"""Matrix Market (MTX) support — honoring header attributes.

The paper notes PIGO *disregards* MTX attributes (symmetric graphs are only
half-loaded, under-reporting runtimes).  We parse the banner properly:

  %%MatrixMarket matrix coordinate <field> <symmetry>
  % comments...
  <rows> <cols> <nnz>

field: real|integer|pattern (pattern -> unweighted), symmetry:
general|symmetric (symmetric -> reverse edges are materialized).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .csr import convert_to_csr
from .types import CSR, EdgeList, GraphMeta


@dataclasses.dataclass(frozen=True)
class MtxHeader:
    meta: GraphMeta
    body_offset: int          # byte offset of the first entry line
    rows: int
    cols: int


def read_header(path: str) -> MtxHeader:
    # open_stream decompresses gzip/framed MTX transparently; tell() is in
    # uncompressed coordinates, so body_offset means the same thing either
    # way (the engines apply offsets after decompression too).
    from .codecs import open_stream
    with open_stream(path) as f:
        banner = f.readline()
        if not banner.startswith(b"%%MatrixMarket"):
            raise ValueError(f"{path}: missing MatrixMarket banner")
        parts = banner.decode().strip().lower().split()
        if len(parts) < 5 or parts[1] != "matrix" or parts[2] != "coordinate":
            raise ValueError(f"{path}: unsupported banner {banner!r}")
        field, symmetry = parts[3], parts[4]
        if field not in ("real", "integer", "pattern"):
            raise ValueError(f"{path}: unsupported field {field!r}")
        if symmetry not in ("general", "symmetric"):
            raise ValueError(f"{path}: unsupported symmetry {symmetry!r}")
        pos = f.tell()
        line = f.readline()
        while line.startswith(b"%"):
            pos = f.tell()
            line = f.readline()
        rows, cols, nnz = (int(x) for x in line.split()[:3])
        body = f.tell()
    meta = GraphMeta(
        num_vertices=max(rows, cols),
        num_edges=nnz,
        weighted=field in ("real", "integer"),
        symmetric=symmetry == "symmetric",
        base=1,
        pattern=field == "pattern",
    )
    return MtxHeader(meta, body, rows, cols)


def _read_body(path: str, hdr: MtxHeader, engine: str, **kw) -> EdgeList:
    """Parse entries after the header via the unified loader.

    The header/size lines contain numbers that would parse as edges, so
    we hand the engine ``offset=body_offset``; comment lines inside the
    body are rejected by the parser's bad-char line mask anyway.
    """
    from .loader import load_edgelist
    engine = "device" if engine == "jax" else engine   # legacy alias
    el = load_edgelist(path, engine=engine, weighted=hdr.meta.weighted,
                       base=1, num_vertices=hdr.meta.num_vertices,
                       offset=hdr.body_offset, **kw)
    return el


def read_mtx(path: str, *, engine: str = "numpy", **engine_kw) -> EdgeList:
    """Read an MTX file to an EdgeList, honoring field/symmetry."""
    hdr = read_header(path)
    el = _read_body(path, hdr, engine, **engine_kw)
    if int(el.num_edges) != hdr.meta.num_edges:
        raise ValueError(
            f"{path}: parsed {int(el.num_edges)} entries, header says "
            f"{hdr.meta.num_edges}")
    if hdr.meta.symmetric:
        from .edgelist import symmetrize
        n = int(el.num_edges)
        src, dst = np.asarray(el.src[:n]), np.asarray(el.dst[:n])
        keep = src != dst                     # do not duplicate self-loops
        rs, rd = dst[keep], src[keep]
        w = el.weights
        if w is not None:
            w = np.concatenate([w[:n], np.asarray(w[:n])[keep]])
        el = EdgeList(np.concatenate([src, rs]), np.concatenate([dst, rd]),
                      w, np.int64(n + keep.sum()), el.num_vertices)
    return el


def read_mtx_csr(path: str, *, engine: str = "numpy") -> CSR:
    return convert_to_csr(read_mtx(path, engine=engine), engine=engine)


def mtx_to_snapshot(path: str, out_path: str, *, engine: str = "numpy",
                    csr: bool = True, compress: str | None = None,
                    compress_level: int | None = None) -> GraphMeta:
    """Convert an MTX file to a binary ``.gvel`` snapshot (parse once).

    Header attributes are honored during the conversion — a symmetric
    MTX is materialized with its reverse edges, a pattern field stays
    unweighted — so the snapshot is the *resolved* graph and reloads
    with no MTX-specific handling at all.  With ``csr=True`` (default)
    a prebuilt CSR is embedded, making ``load_csr(out_path)`` a pure
    mmap.  Returns the source header's :class:`GraphMeta`.

    A thin wrapper over ``open_graph(path).save(out_path, ...)`` — the
    :class:`~repro.core.source.GraphSource` write path.
    """
    from .source import open_graph

    src = open_graph(path, engine=engine)
    if src.format != "mtx":
        raise ValueError(f"{path}: missing MatrixMarket banner")
    src.save(out_path, csr=csr, compress=compress,
             compress_level=compress_level)
    return src._mtx_header().meta


def write_mtx(path: str, src, dst, weights=None, *, num_vertices: int,
              symmetric: bool = False) -> None:
    field = "pattern" if weights is None else "real"
    sym = "symmetric" if symmetric else "general"
    with open(path, "w") as f:
        f.write(f"%%MatrixMarket matrix coordinate {field} {sym}\n")
        f.write(f"% generated by repro.core.mtx\n")
        f.write(f"{num_vertices} {num_vertices} {len(src)}\n")
        if weights is None:
            for u, v in zip(src, dst):
                f.write(f"{u + 1} {v + 1}\n")
        else:
            for u, v, w in zip(src, dst, weights):
                f.write(f"{u + 1} {v + 1} {w}\n")
