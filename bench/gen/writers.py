"""Input writers of the benchmark, vectorised (no per-line Python).

``write_text`` writes one ``u v`` (or ``u v w``, ``w`` a whole number)
per line, ids shifted by ``base``, every line ended by a newline: the
bytes of the program's ``core/generate.write_edgelist`` for an
unweighted graph.  ``write_gvel`` gives the bytes of
``core/snapshot.save_snapshot`` for an edgelist-only graph: a version-1
``.gvel`` laid out as ``docs/snapshot-format.md`` sets out.
"""
from __future__ import annotations

import struct

import numpy as np

_NL, _SP, _ZERO = 10, 32, 48


def _ndigits(x: np.ndarray) -> np.ndarray:
    n = np.ones(x.shape, np.int8)
    bound = 10
    while bound <= int(x.max(initial=0)):
        n += x >= bound
        bound *= 10
    return n


def _digits_right_aligned(x: np.ndarray, width: int) -> np.ndarray:
    """(len(x), width) ASCII digits of ``x``, right-aligned, zero-filled."""
    out = np.empty((x.shape[0], width), np.uint8)
    y = x.astype(np.uint32)
    for k in range(width - 1, -1, -1):
        out[:, k] = _ZERO + y % 10
        y //= 10
    return out


def _number_field(x: np.ndarray):
    """(chars, keep): the decimal digits of ``x``, one row each."""
    n = _ndigits(x)
    width = int(n.max(initial=1))
    chars = _digits_right_aligned(x, width)
    return chars, np.arange(width)[None, :] >= (width - n)[:, None]


def text_bytes(src, dst, weights=None, *, base: int = 1) -> np.ndarray:
    """The edgelist text as one uint8 array."""
    cols = [np.asarray(src, np.int64) + base, np.asarray(dst, np.int64) + base]
    if weights is not None:
        cols.append(np.asarray(weights, np.int64))
    lo = min((c.min(initial=0) for c in cols), default=0)
    hi = max((c.max(initial=0) for c in cols), default=0)
    if lo < 0 or hi >= 2**32:
        raise ValueError("ids (after the base) and weights must lie in [0, 2^32)")
    n = cols[0].shape[0]
    chars, masks = [], []
    for k, x in enumerate(cols):
        c, m = _number_field(x)
        sep = _NL if k == len(cols) - 1 else _SP
        chars += [c, np.full((n, 1), sep, np.uint8)]
        masks += [m, np.ones((n, 1), bool)]
    return np.concatenate(chars, axis=1)[np.concatenate(masks, axis=1)]


def write_text(path: str, src, dst, weights=None, *, base: int = 1,
               pad_to: int | None = None) -> int:
    """Write the edgelist; with ``pad_to``, append empty lines up to
    exactly that many bytes.  Returns the bytes written."""
    data = text_bytes(src, dst, weights, base=base)
    size = data.shape[0]
    if pad_to is not None and size > pad_to:
        raise ValueError(f"text of {size} bytes does not fit pad_to={pad_to}")
    with open(path, "wb") as f:
        f.write(memoryview(data))
        if pad_to is not None and pad_to > size:
            f.write(b"\n" * (pad_to - size))
    return size if pad_to is None else pad_to


_MAGIC = b"GVELSNAP"
_HEADER = "<8sIIQQII"       # magic, version, flags, V, E, sections, reserved
_ENTRY = "<IIQQ"            # section id, dtype code, offset, nbytes
_ALIGN = 4096
_FLAG_WEIGHTED, _FLAG_EDGELIST = 1, 2


def write_gvel(path: str, src, dst, num_vertices: int, weights=None) -> int:
    """Write an edgelist-only version-1 ``.gvel``; returns its size."""
    sections = [(1, 1, np.ascontiguousarray(src, "<i4")),
                (2, 1, np.ascontiguousarray(dst, "<i4"))]
    flags = _FLAG_EDGELIST
    if weights is not None:
        sections.append((3, 3, np.ascontiguousarray(weights, "<f4")))
        flags |= _FLAG_WEIGHTED
    table = []
    off = struct.calcsize(_HEADER) + len(sections) * struct.calcsize(_ENTRY)
    for sid, code, arr in sections:
        off = -(-off // _ALIGN) * _ALIGN
        table.append((sid, code, off, arr.nbytes))
        off += arr.nbytes
    with open(path, "wb") as f:
        f.write(struct.pack(_HEADER, _MAGIC, 1, flags, int(num_vertices),
                            int(sections[0][2].shape[0]), len(sections), 0))
        for entry in table:
            f.write(struct.pack(_ENTRY, *entry))
        for (_sid, _code, arr), entry in zip(sections, table):
            f.seek(entry[2])
            f.write(memoryview(arr))
        f.truncate(off)
    return off
