"""Binary graph snapshots: the ``.gvel`` container (write once, load many).

GVEL's CSR speedups come from paying the text-parse cost exactly once;
every load after that should be a zero-parse mmap.  This module defines
a versioned little-endian container holding the packed edgelist buffers
(``src``/``dst``/optional ``w``) and, optionally, a prebuilt CSR
(``offsets``/``indices``/optional ``weights``) so ``load_csr`` can skip
even the rank-based build — the true "write once, load many" fast path.

Layout (all integers little-endian; byte-level spec in
``docs/snapshot-format.md``)::

    [ header  | section table | pad | section 0 | pad | section 1 | ... ]

    header (40 bytes):
        magic     8s   b"GVELSNAP"
        version   u32  1 (raw sections) or 2 (sections may be compressed)
        flags     u32  bit 0 WEIGHTED, bit 1 HAS_EDGELIST, bit 2 HAS_CSR
        num_vertices  u64
        num_edges     u64
        section_count u32
        reserved      u32  (must be 0)
    section table entry (v1, 24 bytes each):
        section_id u32, dtype_code u32, offset u64, nbytes u64
    section table entry (v2, 40 bytes each):
        v1 fields + codec_id u32 (0 = stored), reserved u32,
        raw_nbytes u64; compressed payloads are ``core.codecs`` frame
        streams (per-frame lengths + CRC32)

Every section starts on a 4096-byte (page) boundary so an mmap'd reader
hands out aligned, typed, read-only views with no copying and no
parsing.  Compressed v2 section payloads decode **lazily, per
section**: a both-sections snapshot opened for its prebuilt CSR never
decompresses its edgelist frames (``read_snapshot(path, eager=False)``;
the default ``eager=True`` keeps the historical decompress-at-open
contract).  Vertex ids in a snapshot are canonical **0-based**
regardless of the base of the text file it was converted from.

Readers must reject unknown versions and truncated files, and must
*ignore* unknown section ids (that is how the format grows without a
version bump — see the spec for the bump rules).

The :class:`SnapshotEngine` registered under ``"snapshot"`` plugs this
into the loader registry: ``read_edgelist`` returns mmap-backed views,
``stream`` feeds the fused ``load_csr`` device path, and
``read_csr_prebuilt`` serves an embedded CSR with no build at all.
"""
from __future__ import annotations

import os
import struct
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from .blocks import mmap_bytes
from .types import CSR, EdgeList

MAGIC = b"GVELSNAP"
VERSION = 1                        # written when no v2 feature is used
VERSION_COMPRESSED = 2             # v2: section table entries carry a codec
SUPPORTED_VERSIONS = (VERSION, VERSION_COMPRESSED)
HEADER_FMT = "<8sIIQQII"           # magic, version, flags, V, E, n_sections, reserved
HEADER_LEN = struct.calcsize(HEADER_FMT)       # 40
SECTION_FMT = "<IIQQ"              # id, dtype code, byte offset, byte length
SECTION_LEN = struct.calcsize(SECTION_FMT)     # 24
# v2 entry: v1 fields + codec id, reserved (0), uncompressed byte length
SECTION_FMT_V2 = "<IIQQIIQ"
SECTION_LEN_V2 = struct.calcsize(SECTION_FMT_V2)   # 40
ALIGN = 4096                       # sections are page-aligned

# Per-section budget for decoded-frame memos on the selective-read path
# (get_slice).  A long-lived handle serving point reads against a large
# compressed section would otherwise accumulate every frame it ever
# touched — the decoded payload re-assembled piecemeal, pinned by the
# serving cache.  Least-recently-used frames are dropped past the cap
# (re-decode on next touch); evictions are counted and surfaced through
# Snapshot.frame_cache_stats() / SourceCache.stats().  Tests (and
# memory-constrained servers) may lower this module global.
FRAME_CACHE_BYTES = 32 * 1024 * 1024

FLAG_WEIGHTED = 1 << 0
FLAG_EDGELIST = 1 << 1
FLAG_CSR = 1 << 2

SEC_SRC = 1
SEC_DST = 2
SEC_EDGE_WEIGHTS = 3
SEC_CSR_OFFSETS = 4
SEC_CSR_INDICES = 5
SEC_CSR_WEIGHTS = 6

SECTION_NAMES = {
    SEC_SRC: "src",
    SEC_DST: "dst",
    SEC_EDGE_WEIGHTS: "edge_weights",
    SEC_CSR_OFFSETS: "csr_offsets",
    SEC_CSR_INDICES: "csr_indices",
    SEC_CSR_WEIGHTS: "csr_weights",
}

# dtype codes are explicit little-endian; a snapshot means the same bytes
# on every host (big-endian writers must byteswap before writing).
_CODE_TO_DTYPE = {
    1: np.dtype("<i4"),
    2: np.dtype("<i8"),
    3: np.dtype("<f4"),
    4: np.dtype("<f8"),
    5: np.dtype("u1"),
}
_KIND_TO_CODE = {("i", 4): 1, ("i", 8): 2, ("f", 4): 3, ("f", 8): 4,
                 ("u", 1): 5}


class SnapshotError(ValueError):
    """Malformed, truncated, or unsupported ``.gvel`` file.

    ``section`` names the damaged section (``"csr_indices"``, ...) when
    the failure is a payload decode — the quarantine key the serving
    cache uses to keep other sections of the same file live — and is
    ``None`` for structural damage (bad magic, truncated table)."""

    def __init__(self, message: str, *, section: Optional[str] = None):
        super().__init__(message)
        self.section = section


def _dtype_code(dtype: np.dtype) -> int:
    try:
        return _KIND_TO_CODE[(dtype.kind, dtype.itemsize)]
    except KeyError:
        raise SnapshotError(f"unsupported section dtype {dtype}") from None


def _align(off: int) -> int:
    return -(-off // ALIGN) * ALIGN


def is_snapshot(path: str) -> bool:
    """Cheap magic sniff; False for missing/short/non-snapshot files."""
    try:
        with open(path, "rb") as f:
            return f.read(len(MAGIC)) == MAGIC
    except OSError:
        return False


def peek_header(path: str) -> Tuple[int, int, int, int, int]:
    """Validate and return (version, flags, V, E, section_count) without
    touching any section bytes — used for cheap num_vertices hints."""
    size = os.path.getsize(path)
    if size < HEADER_LEN:
        raise SnapshotError(f"{path}: truncated header ({size} bytes)")
    with open(path, "rb") as f:
        hdr = f.read(HEADER_LEN)
    magic, version, flags, v, e, count, reserved = struct.unpack(HEADER_FMT, hdr)
    if magic != MAGIC:
        raise SnapshotError(f"{path}: bad magic {magic!r}, not a .gvel snapshot")
    if version not in SUPPORTED_VERSIONS:
        raise SnapshotError(
            f"{path}: unsupported snapshot version {version} "
            f"(this reader supports {SUPPORTED_VERSIONS})")
    if reserved != 0:
        raise SnapshotError(f"{path}: nonzero reserved header field")
    return version, flags, v, e, count


def peek_table(path: str):
    """Header + section-table metadata without touching payload bytes:
    ``(version, flags, V, E, entries)`` where each entry is
    ``(sid, dtype_code, offset, nbytes, codec_id, raw_nbytes)``.

    The cheap introspection primitive behind ``GraphSource.info()`` —
    reads ``HEADER_LEN + count * entry_len`` bytes, nothing else."""
    version, flags, v, e, count = peek_header(path)
    v2 = version == VERSION_COMPRESSED
    entry_fmt = SECTION_FMT_V2 if v2 else SECTION_FMT
    entry_len = SECTION_LEN_V2 if v2 else SECTION_LEN
    table_len = count * entry_len
    with open(path, "rb") as f:
        f.seek(HEADER_LEN)
        raw = f.read(table_len)
    if len(raw) < table_len:
        raise SnapshotError(
            f"{path}: truncated section table "
            f"({HEADER_LEN + len(raw)} < {HEADER_LEN + table_len} bytes)")
    entries = []
    for i in range(count):
        if v2:
            sid, code, off, nbytes, codec_id, _rsvd, raw_nbytes = \
                struct.unpack_from(entry_fmt, raw, i * entry_len)
        else:
            sid, code, off, nbytes = struct.unpack_from(entry_fmt, raw,
                                                        i * entry_len)
            codec_id, raw_nbytes = 0, nbytes
        entries.append((sid, code, off, nbytes, codec_id, raw_nbytes))
    return version, flags, v, e, entries


def section_frame_counts(path: str) -> Dict[str, int]:
    """Per-section frame counts for a snapshot's *compressed* sections:
    ``{section_name: frame_count}`` (empty for v1 / all-raw files).

    Reads the header, the section table, and each compressed section's
    12-byte frame headers (``codecs.frame_table`` walks them, skipping
    every compressed payload) — never decompresses anything.  This is
    the partial-decode planner's view of the file, surfaced through
    ``GraphSource.info()``.
    """
    from . import codecs
    _version, _flags, _v, _e, entries = peek_table(path)
    out: Dict[str, int] = {}
    data = None
    for sid, _code, off, nbytes, codec_id, _raw in entries:
        if codec_id == 0 or sid not in SECTION_NAMES:
            continue
        if data is None:
            data = mmap_bytes(path)
        out[SECTION_NAMES[sid]] = codecs.count_frames(
            data[off:off + nbytes],
            context=f"{path} section {sid}")
    return out


# ---------------------------------------------------------------------------
# writer
# ---------------------------------------------------------------------------

def save_snapshot(
    path: str,
    *,
    edgelist: Optional[EdgeList] = None,
    csr: Optional[CSR] = None,
    compress: Optional[str] = None,
    compress_level: Optional[int] = None,
    frame_beta: Optional[int] = None,
) -> None:
    """Write a ``.gvel`` snapshot from loader outputs.

    At least one of ``edgelist`` / ``csr`` is required; pass both to get
    a file that serves *every* ``load_*`` entry point (``load_csr``
    prefers the embedded CSR and skips the build entirely).  Vertex ids
    are stored as-is — loader outputs are already 0-based.  A CSR must
    be global (``row_start == 0``); shard-local CSRs have no file-level
    meaning.

    ``compress`` names a registered codec (``"zlib"``, ``"zstd"`` when
    available); section payloads are then stored as checksummed frame
    streams (``core.codecs``) and the file is written as version 2.
    With ``compress=None`` (default) the output is a byte-identical
    version-1 file — readable by any v1 reader.
    """
    if edgelist is None and csr is None:
        raise ValueError("save_snapshot needs an edgelist, a csr, or both")

    sections: List[Tuple[int, np.ndarray]] = []
    flags = 0
    num_vertices = None
    num_edges = None

    if edgelist is not None:
        n = int(edgelist.num_edges)
        src = np.ascontiguousarray(np.asarray(edgelist.src[:n], dtype="<i4"))
        dst = np.ascontiguousarray(np.asarray(edgelist.dst[:n], dtype="<i4"))
        sections += [(SEC_SRC, src), (SEC_DST, dst)]
        if edgelist.weights is not None:
            w = np.ascontiguousarray(np.asarray(edgelist.weights[:n],
                                                dtype="<f4"))
            sections.append((SEC_EDGE_WEIGHTS, w))
            flags |= FLAG_WEIGHTED
        flags |= FLAG_EDGELIST
        num_vertices = int(edgelist.num_vertices)
        num_edges = n

    if csr is not None:
        if csr.row_start != 0:
            raise ValueError("save_snapshot: shard-local CSR (row_start != 0) "
                             "cannot be snapshotted")
        offsets = np.ascontiguousarray(np.asarray(csr.offsets, dtype="<i8"))
        indices = np.ascontiguousarray(np.asarray(csr.targets, dtype="<i4"))
        if offsets.shape[0] != csr.num_vertices + 1:
            raise ValueError(
                f"save_snapshot: offsets length {offsets.shape[0]} != "
                f"num_vertices + 1 ({csr.num_vertices + 1})")
        if num_vertices is not None and num_vertices != csr.num_vertices:
            raise ValueError(
                f"save_snapshot: edgelist has {num_vertices} vertices, "
                f"csr has {csr.num_vertices}")
        if num_edges is not None and num_edges != indices.shape[0]:
            raise ValueError(
                f"save_snapshot: edgelist has {num_edges} edges, "
                f"csr has {indices.shape[0]} — snapshot one graph")
        csr_weighted = csr.weights is not None
        if edgelist is not None and csr_weighted != (edgelist.weights is not None):
            raise ValueError("save_snapshot: edgelist/csr weight presence "
                             "mismatch")
        sections += [(SEC_CSR_OFFSETS, offsets), (SEC_CSR_INDICES, indices)]
        if csr_weighted:
            cw = np.ascontiguousarray(np.asarray(csr.weights, dtype="<f4"))
            sections.append((SEC_CSR_WEIGHTS, cw))
            flags |= FLAG_WEIGHTED
        flags |= FLAG_CSR
        num_vertices = csr.num_vertices
        if num_edges is None:
            num_edges = int(indices.shape[0])

    if compress is not None:
        from . import codecs
        codec = codecs.get_codec(compress)
        beta = codecs.DEFAULT_FRAME_BETA if frame_beta is None else frame_beta
        version = VERSION_COMPRESSED
        payloads = [(sid, arr,
                     codecs.compress_frames(arr.tobytes(), codec,
                                            level=compress_level,
                                            frame_beta=beta))
                    for sid, arr in sections]
    else:
        codec = None
        version = VERSION
        payloads = [(sid, arr, None) for sid, arr in sections]

    # layout: header, table, then page-aligned sections in table order
    entry_len = SECTION_LEN if version == VERSION else SECTION_LEN_V2
    table = []
    off = HEADER_LEN + len(sections) * entry_len
    for sid, arr, comp in payloads:
        off = _align(off)
        stored = arr.nbytes if comp is None else len(comp)
        if version == VERSION:
            table.append((sid, _dtype_code(arr.dtype), off, stored))
        else:
            table.append((sid, _dtype_code(arr.dtype), off, stored,
                          codec.codec_id, 0, arr.nbytes))
        off += stored
    end = off

    with open(path, "wb") as f:
        f.write(struct.pack(HEADER_FMT, MAGIC, version, flags,
                            num_vertices, num_edges, len(sections), 0))
        fmt = SECTION_FMT if version == VERSION else SECTION_FMT_V2
        for entry in table:
            f.write(struct.pack(fmt, *entry))
        for (sid, arr, comp), entry in zip(payloads, table):
            f.seek(entry[2])
            f.write(arr.tobytes() if comp is None else comp)
        # zero-length tail sections may point past the last written byte;
        # extend so every (offset, offset + nbytes) range is in-file
        f.truncate(end)


# ---------------------------------------------------------------------------
# reader
# ---------------------------------------------------------------------------

class _Section:
    """One section's payload cell.

    Uncompressed sections are materialized at table-parse time as
    zero-copy mmap views (the mmap itself is lazy — the kernel pages
    bytes in on first touch).  Compressed sections hold only their frame
    stream's byte range; :meth:`get` decodes (and CRC-checks) the
    payload on first access and memoizes the result, so a section the
    caller never touches is never decompressed — and corruption in it
    is never noticed (the deferred-error trade documented in
    ``docs/api.md``).

    :meth:`get_slice` is the selective-read path below :meth:`get`: an
    element range of an uncompressed section is a zero-copy sub-view,
    and an element range of a *compressed* section decodes only the
    frames its byte span overlaps (the frame headers form a seek index
    — ``codecs.frame_table``), caching decoded frames per frame so a
    stream of point reads never re-pays a frame's decompression.
    Decode paths are lock-guarded: concurrent readers of one section
    (the query-service cache shares handles across threads) each see
    fully-decoded, immutable arrays.
    """

    __slots__ = ("path", "sid", "dtype", "offset", "nbytes", "codec",
                 "raw_nbytes", "_data", "_arr", "_lock", "_ftable",
                 "_frames", "_frames_bytes", "_frame_hits",
                 "_frame_evictions")

    def __init__(self, path, sid, dtype, offset, nbytes, codec,
                 raw_nbytes, data):
        self.path = path
        self.sid = sid
        self.dtype = dtype
        self.offset = offset
        self.nbytes = nbytes
        self.codec = codec               # None = stored (codec_id 0)
        self.raw_nbytes = raw_nbytes
        self._data = data
        self._arr = (data[offset:offset + nbytes].view(dtype)
                     if codec is None else None)
        self._lock = threading.Lock()
        self._ftable = None              # codecs.FrameEntry seek index
        # frame idx -> raw bytes, LRU order, bounded by FRAME_CACHE_BYTES
        self._frames: "OrderedDict[int, np.ndarray]" = OrderedDict()
        self._frames_bytes = 0
        self._frame_hits = 0
        self._frame_evictions = 0

    @property
    def length(self) -> int:
        """Element count, known from the table alone (no payload)."""
        return self.raw_nbytes // self.dtype.itemsize

    @property
    def decoded(self) -> bool:
        return self._arr is not None

    def get(self) -> np.ndarray:
        if self._arr is None:
            with self._lock:
                if self._arr is not None:       # decoded while waiting
                    return self._arr
                # dynamic attribute lookup so tests can instrument the
                # decode path (repro.core.codecs.decompress_frames)
                from . import codecs
                try:
                    arr = codecs.decompress_frames(
                        self._data[self.offset:self.offset + self.nbytes],
                        self.raw_nbytes, self.codec,
                        context=f"{self.path} section {self.sid}")
                except ValueError as exc:
                    raise SnapshotError(
                        str(exc),
                        section=SECTION_NAMES.get(self.sid)) from None
                arr.flags.writeable = False  # parity with the mmap views
                self._frames.clear()         # full decode supersedes frames
                self._frames_bytes = 0
                self._arr = arr.view(self.dtype)
        return self._arr

    def _frame_table(self):
        if self._ftable is None:
            from . import codecs
            try:
                self._ftable = codecs.frame_table(
                    self._data[self.offset:self.offset + self.nbytes],
                    context=f"{self.path} section {self.sid}")
            except ValueError as exc:
                raise SnapshotError(
                    str(exc), section=SECTION_NAMES.get(self.sid)) from None
        return self._ftable

    def get_slice(self, lo: int, hi: int) -> np.ndarray:
        """Elements ``[lo, hi)`` of this section.

        Uncompressed (and already-fully-decoded) sections return a
        zero-copy sub-view.  Compressed sections decode **only the
        frames overlapping the element range's byte span** — resolved
        through the frame-header seek index, each decoded frame cached
        on the cell — and assemble the slice from them.  Corruption in
        frames the range never touches is never noticed (the partial
        analogue of the per-section deferred-error trade).
        """
        if not 0 <= lo <= hi <= self.length:
            raise IndexError(
                f"{self.path} section {self.sid}: element range "
                f"[{lo}, {hi}) outside [0, {self.length})")
        if self._arr is not None:
            return self._arr[lo:hi]
        isz = self.dtype.itemsize
        byte_lo, byte_hi = lo * isz, hi * isz
        if byte_lo == byte_hi:
            return np.empty(0, self.dtype)
        from . import codecs
        with self._lock:
            if self._arr is not None:           # raced with a full get()
                return self._arr[lo:hi]
            entries = self._frame_table()
            touched = codecs.frames_overlapping(entries, byte_lo, byte_hi)
            if not touched or touched[0].raw_off > byte_lo \
                    or touched[-1].raw_end < byte_hi:
                raise SnapshotError(
                    f"{self.path} section {self.sid}: frames cover "
                    f"{self.raw_nbytes} bytes but byte range "
                    f"[{byte_lo}, {byte_hi}) is not fully framed",
                    section=SECTION_NAMES.get(self.sid))
            payload = self._data[self.offset:self.offset + self.nbytes]
            parts = []
            for entry in touched:
                raw = self._frames.get(entry.index)
                if raw is None:
                    try:
                        # dynamic lookup: tests instrument decode_frame
                        # to assert ONLY the touched frames decode
                        raw = np.frombuffer(codecs.decode_frame(
                            payload, entry, self.codec,
                            context=f"{self.path} section {self.sid}"),
                            np.uint8)
                    except ValueError as exc:
                        raise SnapshotError(
                            str(exc),
                            section=SECTION_NAMES.get(self.sid)) from None
                    self._frames[entry.index] = raw
                    self._frames_bytes += raw.nbytes
                    # LRU bound: drop coldest memos past the byte cap.
                    # ``parts`` still references this read's frames, so
                    # eviction only forgets, never corrupts, the slice
                    # being assembled.
                    cap = max(int(FRAME_CACHE_BYTES), 0)
                    while self._frames_bytes > cap and len(self._frames) > 1:
                        _, old = self._frames.popitem(last=False)
                        self._frames_bytes -= old.nbytes
                        self._frame_evictions += 1
                else:
                    self._frame_hits += 1
                    self._frames.move_to_end(entry.index)
                parts.append(raw)
            base = touched[0].raw_off
            buf = parts[0] if len(parts) == 1 else np.concatenate(parts)
            out = buf[byte_lo - base:byte_hi - base].view(self.dtype)
            out.flags.writeable = False
            return out


class Snapshot:
    """A validated, mmap-backed handle on a ``.gvel`` file.

    Structure (header, section table, section presence and lengths) is
    validated at open without touching any payload bytes.  Payload
    access is **lazy per section**: v1 / uncompressed sections are
    zero-copy views straight into the page cache, compressed v2
    sections are decompressed — and checksummed — on first access of
    the corresponding property (``src``/``dst``/``edge_weights``/
    ``csr_offsets``/``csr_indices``/``csr_weights``) and memoized.
    Touching only the CSR properties of a both-sections snapshot never
    decodes the edgelist frame streams (and vice versa).

    The trade: corruption inside a compressed payload surfaces at first
    access of *that section* (as :class:`SnapshotError`), not at open.
    Call :meth:`materialize` — or use ``read_snapshot(path)``, which is
    eager by default — to force every checksum up front.
    """

    def __init__(self, path: str, version: int, flags: int,
                 num_vertices: int, num_edges: int,
                 sections: "dict[int, _Section]"):
        self.path = path
        self.version = version
        self.flags = flags
        self.num_vertices = num_vertices
        self.num_edges = num_edges
        self._sections = sections

    def _get(self, sid: int) -> Optional[np.ndarray]:
        cell = self._sections.get(sid)
        if cell is None:
            return None
        first = not cell.decoded
        arr = cell.get()
        if first and sid == SEC_CSR_OFFSETS:
            try:
                self._check_csr_offsets(arr)
            except SnapshotError:
                # stay fatal on retry: a memoized-but-inconsistent array
                # must never be served by the next access
                cell._arr = None
                raise
        return arr

    def _check_csr_offsets(self, arr: np.ndarray) -> None:
        if arr.shape[0] and int(arr[-1]) != self.num_edges:
            raise SnapshotError(
                f"{self.path}: csr offsets end at {int(arr[-1])}, "
                f"header says {self.num_edges} edges",
                section="csr_offsets")

    # lazy payload properties ------------------------------------------------
    @property
    def src(self) -> Optional[np.ndarray]:
        return self._get(SEC_SRC)

    @property
    def dst(self) -> Optional[np.ndarray]:
        return self._get(SEC_DST)

    @property
    def edge_weights(self) -> Optional[np.ndarray]:
        return self._get(SEC_EDGE_WEIGHTS)

    @property
    def csr_offsets(self) -> Optional[np.ndarray]:
        return self._get(SEC_CSR_OFFSETS)

    @property
    def csr_indices(self) -> Optional[np.ndarray]:
        return self._get(SEC_CSR_INDICES)

    @property
    def csr_weights(self) -> Optional[np.ndarray]:
        return self._get(SEC_CSR_WEIGHTS)

    # ------------------------------------------------------------------------
    @property
    def weighted(self) -> bool:
        return bool(self.flags & FLAG_WEIGHTED)

    @property
    def has_edgelist(self) -> bool:
        return bool(self.flags & FLAG_EDGELIST)

    @property
    def has_csr(self) -> bool:
        return bool(self.flags & FLAG_CSR)

    def decoded_sections(self) -> "list[int]":
        """Section ids whose payloads have been materialized (for
        uncompressed sections that is every present id — views cost
        nothing).  Instrumentation hook for tests and benchmarks."""
        return sorted(sid for sid, c in self._sections.items() if c.decoded)

    def section_codecs(self) -> "list[str]":
        """Distinct codec names used by compressed sections."""
        return sorted({c.codec.name for c in self._sections.values()
                       if c.codec is not None})

    def frame_cache_stats(self) -> Dict[str, int]:
        """Decoded-frame memo counters summed over sections:
        ``frames`` / ``bytes`` currently held (bounded per section by
        ``FRAME_CACHE_BYTES``), ``hits`` (reads served from a memo) and
        ``evictions`` (memos dropped past the cap) since open.  The
        serving cache (:meth:`repro.core.cache.SourceCache.stats`)
        aggregates this across its hot handles."""
        out = {"frames": 0, "bytes": 0, "hits": 0, "evictions": 0}
        for c in self._sections.values():
            out["frames"] += len(c._frames)
            out["bytes"] += c._frames_bytes
            out["hits"] += c._frame_hits
            out["evictions"] += c._frame_evictions
        return out

    def materialize(self) -> "Snapshot":
        """Force-decode (and checksum) every section; returns self.
        After this, corruption anywhere in the file has either raised
        or cannot exist — the eager ``read_snapshot`` contract."""
        for sid in sorted(self._sections):
            self._get(sid)
        return self

    def edgelist(self) -> EdgeList:
        if not self.has_edgelist:
            raise SnapshotError(f"{self.path}: CSR-only snapshot has no "
                                f"edgelist sections")
        return EdgeList(self.src, self.dst, self.edge_weights,
                        np.int64(self.num_edges), self.num_vertices)

    def csr(self) -> CSR:
        if not self.has_csr:
            raise SnapshotError(f"{self.path}: snapshot has no CSR sections")
        return CSR(self.csr_offsets, self.csr_indices, self.csr_weights,
                   self.num_vertices)

    # selective reads --------------------------------------------------------
    def _offsets_slice(self, lo: int, hi: int) -> np.ndarray:
        """``offsets[lo:hi+1]`` via partial decode, with the same
        consistency guarantees the full read enforces, scoped to the
        slice: monotone, within ``[0, num_edges]``."""
        off = self._sections[SEC_CSR_OFFSETS].get_slice(lo, hi + 1)
        # point reads slice 2-3 elements; ufunc dispatch would dominate
        # them, so check tiny slices in plain Python
        bad = False
        if off.size:
            if int(off[0]) < 0 or int(off[-1]) > self.num_edges:
                bad = True
            elif off.size <= 4:
                prev = int(off[0])
                for x in off[1:]:
                    x = int(x)
                    if x < prev:
                        bad = True
                        break
                    prev = x
            else:
                bad = bool(np.any(np.diff(off) < 0))
        if bad:
            raise SnapshotError(
                f"{self.path}: csr offsets [{lo}, {hi}] are inconsistent "
                f"(non-monotone or outside [0, {self.num_edges}])")
        return off

    def csr_rows(self, lo: int, hi: int, *,
                 weighted: Optional[bool] = None) -> CSR:
        """The CSR restricted to vertex rows ``[lo, hi)``, decoding (and
        for raw snapshots, touching) only the bytes those rows span.

        Returns a row-local :class:`CSR` — ``offsets`` rebased to 0,
        ``row_start=lo``, global ``num_vertices`` — exactly the
        shard-local layout the distributed loader emits, so
        ``csr.neighbors(u - lo)`` works unchanged.  For uncompressed
        sections the targets/weights come back as zero-copy mmap
        sub-views; compressed sections decode only the frames the row
        range's byte span overlaps (frames are cached per section, so
        repeated point reads are decode-free).  ``weighted=None`` means
        "what the snapshot says".
        """
        if not self.has_csr:
            raise SnapshotError(f"{self.path}: snapshot has no CSR sections")
        if not 0 <= lo <= hi <= self.num_vertices:
            raise IndexError(
                f"{self.path}: row range [{lo}, {hi}) outside "
                f"[0, {self.num_vertices})")
        if weighted is None:
            weighted = self.weighted
        elif weighted and not self.weighted:
            raise SnapshotError(
                f"{self.path}: weighted rows requested but snapshot is "
                f"unweighted")
        off = self._offsets_slice(lo, hi)
        e_lo = int(off[0]) if off.size else 0
        e_hi = int(off[-1]) if off.size else 0
        targets = self._sections[SEC_CSR_INDICES].get_slice(e_lo, e_hi)
        w = (self._sections[SEC_CSR_WEIGHTS].get_slice(e_lo, e_hi)
             if weighted else None)
        local = off if e_lo == 0 else off - np.int64(e_lo)
        return CSR(local, targets, w, self.num_vertices, row_start=lo)

    def neighbors(self, u: int, *, weighted: bool = False):
        """Point lookup: vertex ``u``'s neighbor ids (and weights when
        asked), decoding only the frames the adjacency span touches."""
        row = self.csr_rows(int(u), int(u) + 1, weighted=weighted)
        return (row.targets, row.weights) if weighted else row.targets

    def degree(self, u: int) -> int:
        """Out-degree of ``u`` — touches exactly two offset elements
        (at most the offset frames they fall in)."""
        if not self.has_csr:
            raise SnapshotError(f"{self.path}: snapshot has no CSR sections")
        if not 0 <= int(u) < self.num_vertices:
            raise IndexError(f"{self.path}: vertex {u} outside "
                             f"[0, {self.num_vertices})")
        off = self._offsets_slice(int(u), int(u) + 1)
        return int(off[1]) - int(off[0])


def read_snapshot(path: str, *, eager: bool = True) -> Snapshot:
    """mmap + validate a ``.gvel`` file.

    Structure — header, table, section presence, and element counts —
    is always validated here, *without* reading payload bytes (counts
    come from the table's ``raw_nbytes``).  With ``eager=True`` (the
    default, and the historical contract) every compressed section is
    also decompressed and checksummed before returning, so corruption
    anywhere surfaces at open.  With ``eager=False`` the returned
    :class:`Snapshot` decodes each compressed section on first access
    instead — a both-sections snapshot opened for its prebuilt CSR
    never pays for its edgelist frames (the ``GraphSource`` lazy path;
    see ``docs/api.md`` for the deferred-corruption-error semantics).
    """
    version, flags, num_vertices, num_edges, count = peek_header(path)
    size = os.path.getsize(path)
    v2 = version == VERSION_COMPRESSED
    entry_fmt = SECTION_FMT_V2 if v2 else SECTION_FMT
    entry_len = SECTION_LEN_V2 if v2 else SECTION_LEN
    table_end = HEADER_LEN + count * entry_len
    if size < table_end:
        raise SnapshotError(
            f"{path}: truncated section table ({size} < {table_end} bytes)")
    data = mmap_bytes(path)
    raw = data[HEADER_LEN:table_end].tobytes()

    cells: dict = {}
    for i in range(count):
        if v2:
            sid, code, off, nbytes, codec_id, rsvd, raw_nbytes = \
                struct.unpack_from(entry_fmt, raw, i * entry_len)
            if rsvd != 0:
                raise SnapshotError(f"{path}: section {sid} has nonzero "
                                    f"reserved table field")
        else:
            sid, code, off, nbytes = struct.unpack_from(entry_fmt, raw,
                                                        i * entry_len)
            codec_id, raw_nbytes = 0, nbytes
        if sid not in (SEC_SRC, SEC_DST, SEC_EDGE_WEIGHTS, SEC_CSR_OFFSETS,
                       SEC_CSR_INDICES, SEC_CSR_WEIGHTS):
            continue                    # forward compat: skip unknown sections
        if code not in _CODE_TO_DTYPE:
            raise SnapshotError(f"{path}: section {sid} has unknown dtype "
                                f"code {code}")
        dtype = _CODE_TO_DTYPE[code]
        if off % ALIGN:
            raise SnapshotError(f"{path}: section {sid} offset {off} is not "
                                f"{ALIGN}-byte aligned")
        if off + nbytes > size:
            raise SnapshotError(
                f"{path}: truncated — section {sid} spans "
                f"[{off}, {off + nbytes}) but file is {size} bytes")
        if raw_nbytes % dtype.itemsize:
            raise SnapshotError(f"{path}: section {sid} length {raw_nbytes} "
                                f"is not a multiple of {dtype.itemsize}")
        if codec_id == 0:
            if raw_nbytes != nbytes:
                raise SnapshotError(
                    f"{path}: uncompressed section {sid} declares "
                    f"{raw_nbytes} raw bytes but stores {nbytes}")
            codec = None
        else:
            # the codec must resolve at open (it is table metadata, not
            # payload) — a file needing an uninstalled codec fails fast
            from . import codecs
            try:
                codec = codecs.codec_for_id(codec_id)
            except ValueError as exc:
                raise SnapshotError(f"{path}: section {sid}: {exc}") from None
        cells[sid] = _Section(path, sid, dtype, off, nbytes, codec,
                              raw_nbytes, data)

    def expect(sid: int, name: str, length: int) -> None:
        cell = cells.get(sid)
        if cell is None:
            raise SnapshotError(f"{path}: flagged {name} section missing")
        if cell.length != length:
            raise SnapshotError(f"{path}: {name} has {cell.length} elements, "
                                f"header implies {length}")

    if flags & FLAG_EDGELIST:
        expect(SEC_SRC, "src", num_edges)
        expect(SEC_DST, "dst", num_edges)
        if flags & FLAG_WEIGHTED:
            expect(SEC_EDGE_WEIGHTS, "edge-weights", num_edges)
    if flags & FLAG_CSR:
        expect(SEC_CSR_OFFSETS, "csr-offsets", num_vertices + 1)
        expect(SEC_CSR_INDICES, "csr-indices", num_edges)
        if flags & FLAG_WEIGHTED:
            expect(SEC_CSR_WEIGHTS, "csr-weights", num_edges)
    snap = Snapshot(path, version, flags, num_vertices, num_edges, cells)
    if flags & FLAG_CSR and cells[SEC_CSR_OFFSETS].decoded:
        # uncompressed offsets are views already — check them at open,
        # exactly as the eager reader always did
        snap._check_csr_offsets(cells[SEC_CSR_OFFSETS].get())
    return snap.materialize() if eager else snap


# ---------------------------------------------------------------------------
# loader engine
# ---------------------------------------------------------------------------

class SnapshotEngine:
    """Zero-parse loader engine over ``.gvel`` snapshots.

    ``base`` is accepted for interface parity and ignored — snapshot ids
    are canonical 0-based.  ``offset`` must be 0 (snapshots are never a
    body embedded in another file).
    """

    name = "snapshot"

    def __init__(self):
        self._memo: Optional[Tuple[tuple, Snapshot]] = None

    def _snap(self, path: str) -> Snapshot:
        """One open + validation per file per ``load_csr`` call: the
        front door probes ``read_csr_prebuilt`` / ``num_vertices_hint``
        / ``stream`` in sequence, so memoize on (path, mtime, size).
        A stale entry only costs a re-read.  Snapshots are opened
        *lazily* (``eager=False``): compressed v2 sections decode on
        first access, so serving a prebuilt CSR from a both-sections
        snapshot never decompresses its edgelist frames.  The memo pins
        one mmap plus whatever sections have been decoded so far —
        call :meth:`clear_memo` to release them early.  The (key,
        value) pair is written as one tuple so concurrent loads of
        different files race only on which entry survives, never on a
        mixed key/value.
        """
        from .trace import span

        st = os.stat(path)
        key = (path, st.st_mtime_ns, st.st_size)
        memo = self._memo
        if memo is not None and memo[0] == key:
            return memo[1]
        with span("load.snapshot_read"):
            snap = read_snapshot(path, eager=False)
        self._memo = (key, snap)
        return snap

    def clear_memo(self) -> None:
        """Drop the memoized snapshot (frees a compressed v2 snapshot's
        decompressed arrays; the next load re-reads the file)."""
        self._memo = None

    @staticmethod
    def _check(snap: Snapshot, *, weighted: bool, offset: int) -> None:
        if offset:
            raise ValueError("snapshot engine does not support offset reads")
        if weighted and not snap.weighted:
            raise SnapshotError(
                f"{snap.path}: weighted load requested but snapshot is "
                f"unweighted")

    def read_edgelist(self, path: str, *, weighted: bool = False,
                      base: int = 0, num_vertices: Optional[int] = None,
                      offset: int = 0, **kw) -> EdgeList:
        snap = self._snap(path)
        self._check(snap, weighted=weighted, offset=offset)
        if not snap.has_edgelist:
            raise SnapshotError(f"{snap.path}: CSR-only snapshot has no "
                                f"edgelist sections")
        # touch only what the caller asked for: an unweighted read of a
        # weighted compressed snapshot never decodes the weights section
        w = snap.edge_weights if weighted else None
        v = snap.num_vertices if num_vertices is None else num_vertices
        return EdgeList(snap.src, snap.dst, w, np.int64(snap.num_edges), v)

    def num_vertices_hint(self, path: str) -> int:
        """Header-only |V| — lets the fused ``load_csr`` keep isolated
        trailing vertices a max-id scan over the edges would drop."""
        return self._snap(path).num_vertices

    def stream(self, path: str, *, weighted: bool = False, base: int = 0,
               offset: int = 0, **kw):
        """mmap -> packed device buffers for the fused ``load_csr`` path.

        The buffers are exact-length (no -1 tail padding), which the
        rank-based builders accept: padding handling is a no-op when
        there is none.
        """
        import jax.numpy as jnp

        from .trace import span

        def put(section: np.ndarray):
            with span("load.put"):
                return jnp.asarray(section)

        with span("load.snapshot_read"):
            snap = self._snap(path)
            self._check(snap, weighted=weighted, offset=offset)
            if snap.num_edges > np.iinfo(np.int32).max:
                # Same int32 regime as the text streaming engine's
                # capacity guard: the fused path's running total is a
                # device int32.
                raise ValueError(
                    f"{path}: {snap.num_edges} edges exceeds int32 for the "
                    f"fused load_csr path; embed a prebuilt CSR in the "
                    f"snapshot (scripts/convert.py default) or use "
                    f"load_edgelist")
            if not snap.has_edgelist:
                raise SnapshotError(f"{snap.path}: CSR-only snapshot has no "
                                    f"edgelist sections")
            src = put(snap.src)
            dst = put(snap.dst)
            w = put(snap.edge_weights) if weighted else None
            total = jnp.asarray(snap.num_edges, jnp.int32)
        return (src, dst, w, total), snap.num_edges

    def read_csr_prebuilt(self, path: str, *, weighted: bool = False,
                          num_vertices: Optional[int] = None, offset: int = 0,
                          **kw) -> Optional[CSR]:
        """Embedded-CSR fast path: mmap views, no parse, no build.

        Returns None (caller falls back to the stream + build path) when
        the snapshot has no CSR sections or the caller pinned a
        different ``num_vertices`` than the stored CSR was built for.
        """
        snap = self._snap(path)
        self._check(snap, weighted=weighted, offset=offset)
        if not snap.has_csr:
            return None
        if num_vertices is not None and num_vertices != snap.num_vertices:
            return None
        # section-selective: only the CSR cells decode (never the
        # edgelist frames of a both-sections snapshot), and the weights
        # section only when the caller asked for weights
        return CSR(snap.csr_offsets, snap.csr_indices,
                   snap.csr_weights if weighted else None,
                   snap.num_vertices)

    def read_csr_rows(self, path: str, lo: int, hi: int, *,
                      weighted: bool = False,
                      num_vertices: Optional[int] = None, offset: int = 0,
                      **kw) -> Optional[CSR]:
        """Selective fast path: rows ``[lo, hi)`` straight off the
        snapshot — mmap sub-views for raw sections, frame-selective
        decode for compressed ones.  Returns None (caller slices the
        full product instead) when the snapshot has no CSR sections or
        the caller pinned a conflicting ``num_vertices``."""
        snap = self._snap(path)
        self._check(snap, weighted=weighted, offset=offset)
        if not snap.has_csr:
            return None
        if num_vertices is not None and num_vertices != snap.num_vertices:
            return None
        return snap.csr_rows(lo, hi, weighted=weighted)

    def read_neighbors(self, path: str, u: int, *, weighted: bool = False,
                       num_vertices: Optional[int] = None, offset: int = 0,
                       **kw):
        """Point-lookup fast path: ``(targets, weights-or-None)`` for
        vertex ``u``, or None when no CSR sections are embedded."""
        snap = self._snap(path)
        self._check(snap, weighted=weighted, offset=offset)
        if not snap.has_csr:
            return None
        if num_vertices is not None and num_vertices != snap.num_vertices:
            return None
        row = snap.csr_rows(int(u), int(u) + 1, weighted=weighted)
        return row.targets, row.weights

    def read_degree(self, path: str, u: int, *, weighted: bool = False,
                    num_vertices: Optional[int] = None, offset: int = 0,
                    **kw) -> Optional[int]:
        """Degree fast path: two offset elements, no target bytes."""
        snap = self._snap(path)
        self._check(snap, weighted=weighted, offset=offset)
        if not snap.has_csr:
            return None
        if num_vertices is not None and num_vertices != snap.num_vertices:
            return None
        return snap.degree(u)
