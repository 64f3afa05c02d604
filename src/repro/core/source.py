"""GraphSource: the lazy, introspectable front door for graph loading.

GVEL's thesis is that loading should pay only for what the caller
actually consumes.  This module is where that becomes an API contract:

    from repro.core import open_graph

    src = open_graph("web.gvel")      # resolve format/codec/engine ONCE
    src.info()                        # header-only probe: V/E/codec/size
    src.csr()                         # lazy, memoized; decodes only the
                                      # CSR sections of a .gvel snapshot
    src.edgelist()                    # lazy, memoized
    src.save("web.z.gvel", compress="zlib")   # write-once snapshot path

A :class:`GraphSource` is a cheap handle.  Opening one sniffs the
format (``.gvel`` snapshot magic / MTX banner / plain text) and the
compression codec (gzip / framed, by magic, never extension) exactly
once; every product is computed on first request and memoized on the
handle.  Laziness is real, not cosmetic:

* ``info()`` reads *headers only* — a ``.gvel`` header + section
  table (never payload bytes), an MTX banner + size line, a framed
  container header.  ``info()`` on a multi-MB text edgelist does not
  parse it (plain text has no header, so V/E report as unknown).
* ``csr()`` on a both-sections compressed snapshot decompresses only
  the CSR sections; the edgelist frame streams are never decoded
  (:mod:`repro.core.snapshot` decodes per section, on first access).
* The price of laziness is **deferred corruption errors**: damage
  inside a compressed section payload surfaces (as
  :class:`~repro.core.snapshot.SnapshotError`) at first access of a
  product needing that section, not at ``open_graph``.  Structural
  damage — bad magic, truncated table, unknown codec — still fails at
  open (with ``validate=True``, the default).  See ``docs/api.md``.

The historical free functions (``load_edgelist``/``load_csr``/
``read_edgelist*``/``read_csr``) remain as thin wrappers delegating to
a ``GraphSource``, so existing call sites keep working unchanged.

``python -m repro.core.source <path>`` prints ``info()`` as JSON — a
quick "what is this file?" probe for CI and humans.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .faults import fault_plan
from .loader import (DEFAULT_CSR_ENGINE, DEFAULT_EDGELIST_ENGINE, LoadOptions,
                     available_engines, csr_convert_engine, get_engine,
                     read_csr_sharded_via, read_csr_via, read_edgelist_via,
                     resolve_tuned)
from .trace import span
from .types import CSR, EdgeList

FORMAT_GVEL = "gvel"
FORMAT_MTX = "mtx"
FORMAT_TEXT = "text"

_MTX_BANNER = b"%%MatrixMarket"


def _normalize_rows(rows) -> Tuple[int, int]:
    """``rows`` -> ``(lo, hi)``: a ``range`` with step 1 or a
    ``(lo, hi)`` pair; bounds checked against |V| downstream."""
    if isinstance(rows, range):
        if rows.step != 1:
            raise ValueError(f"rows must have step 1, got {rows!r}")
        return rows.start, max(rows.start, rows.stop)
    try:
        lo, hi = rows
    except (TypeError, ValueError):
        raise ValueError(
            f"rows must be a step-1 range or a (lo, hi) pair, "
            f"got {rows!r}") from None
    lo, hi = int(lo), int(hi)
    if hi < lo:
        raise ValueError(f"rows (lo, hi) must have lo <= hi, got {rows!r}")
    return lo, hi


def slice_csr(csr: CSR, lo: int, hi: int) -> CSR:
    """Vertex rows ``[lo, hi)`` of a global CSR as a row-local CSR:
    ``offsets`` rebased to 0, ``row_start=lo``, global ``num_vertices``
    — the same layout the snapshot partial-read path serves, so the
    fallback (slice the full product) and the fast path (decode only
    the touched frames) are interchangeable."""
    if csr.row_start != 0:
        raise ValueError("slice_csr expects a global CSR (row_start == 0)")
    if not 0 <= lo <= hi <= csr.num_rows:
        raise IndexError(
            f"row range [{lo}, {hi}) outside [0, {csr.num_rows})")
    offsets = np.asarray(csr.offsets)
    off = offsets[lo:hi + 1]
    e_lo = int(off[0]) if off.size else 0
    e_hi = int(off[-1]) if off.size else 0
    local = off if e_lo == 0 else off - off.dtype.type(e_lo)
    targets = np.asarray(csr.targets)[e_lo:e_hi]
    w = None if csr.weights is None else np.asarray(csr.weights)[e_lo:e_hi]
    return CSR(local, targets, w, csr.num_vertices, row_start=lo)


@dataclasses.dataclass(frozen=True)
class SourceInfo:
    """Cheap metadata about a graph file — headers only, no payloads.

    ``None`` means "unknown without parsing": plain text has no header,
    so its ``num_vertices``/``num_edges``/``weighted`` are None, while
    ``.gvel`` and MTX report theirs straight from the header.  For MTX,
    ``num_edges`` is the declared entry count (pre symmetric
    expansion).  ``raw_bytes`` is the uncompressed payload size when a
    header declares it (framed container, ``.gvel`` table, gzip
    trailer hint), else the on-disk size for raw files.
    """

    path: str
    format: str                       # "gvel" | "mtx" | "text"
    codec: Optional[str]              # "gzip" / "framed-zlib" / section codec
    size_bytes: int                   # on-disk size
    raw_bytes: Optional[int]          # uncompressed size, when known
    version: Optional[int]            # .gvel container version
    num_vertices: Optional[int]
    num_edges: Optional[int]
    weighted: Optional[bool]
    symmetric: Optional[bool]         # MTX banner symmetry (None elsewhere)
    has_edgelist: Optional[bool]      # .gvel sections present
    has_csr: Optional[bool]
    engine: Optional[str]             # engine pinned at open (None = default)
    # per-section frame counts of a compressed .gvel's sections
    # ({"csr_offsets": 3, ...}; empty for raw sections, None for non-gvel)
    # — the partial-decode planner's view: a row range decodes only the
    # frames its byte span touches, and this is how many there are.
    section_frames: Optional[Dict[str, int]] = None

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _detect(path: str, offset: int) -> Tuple[str, Optional[str]]:
    """(format, compression-kind) by magic sniff, never extension.

    ``offset != 0`` means the caller is handing us body bytes embedded
    in another container (an MTX body) — never a snapshot or a nested
    MTX, so only the compression sniff applies.  Unreadable/missing
    paths sniff as raw text so non-file engines (tests, RPC) keep
    working; existence is ``validate``'s job.
    """
    from .codecs import compression_of, peek_bytes
    from .snapshot import MAGIC, is_snapshot

    kind = compression_of(path)
    if offset != 0:
        return FORMAT_TEXT, kind
    if is_snapshot(path):
        return FORMAT_GVEL, None
    if kind is not None and peek_bytes(path, len(MAGIC)) == MAGIC:
        # A whole-file-compressed snapshot would decode as text
        # garbage; .gvel v2 compresses *inside* the container.
        raise ValueError(
            f"{path}: externally compressed .gvel snapshot; "
            f"decompress it, or recreate it with internal section "
            f"compression (scripts/convert.py --compress)")
    if peek_bytes(path, len(_MTX_BANNER)) == _MTX_BANNER:
        return FORMAT_MTX, kind
    return FORMAT_TEXT, kind


class GraphSource:
    """A lazy handle on one graph file.

    Construction (via :func:`open_graph`) resolves format, compression
    codec, and engine once; products — :meth:`info`, :meth:`edgelist`,
    :meth:`csr`, :meth:`stream` — are computed on first request and
    memoized on the handle (``src.csr() is src.csr()``).  The handle
    never re-sniffs the file; reopen after rewriting a path.

    Laziness/memoization guarantees and the deferred-corruption-error
    semantics are documented in ``docs/api.md``.
    """

    def __init__(self, path: str, opts: LoadOptions, *, validate: bool = True):
        self.path = str(path)
        fmt, ckind = _detect(self.path, opts.offset)
        if fmt == FORMAT_GVEL:
            # any engine request routes to snapshot: a text parser
            # pointed at a binary snapshot would decode garbage
            opts = opts.replace(engine="snapshot")
        self.options = opts
        self.format = fmt
        self._ckind = ckind                   # "gzip" | "framed" | None
        self._info: Optional[SourceInfo] = None
        self._el: Optional[EdgeList] = None
        self._el_engine: Optional[str] = None
        self._csr: Optional[CSR] = None
        self._sharded_csrs: Dict[Tuple[Any, str], CSR] = {}
        self._mtx_hdr = None
        self._gvel_peek = None                # (version, flags, V, E, entries)
        self._framed_hdr = None               # codecs.FramedInfo
        self._snap = None                     # pinned lazy Snapshot (gvel)
        if validate:
            self._validate()

    def __repr__(self) -> str:
        eng = self.options.engine or "auto"
        codec = f", codec={self._ckind}" if self._ckind else ""
        return (f"GraphSource({self.path!r}, format={self.format}"
                f"{codec}, engine={eng})")

    # -- open-time checks ----------------------------------------------------

    def _validate(self) -> None:
        """Cheap structural validation at open: existence, container
        headers, engine name, section codec ids.  Never touches
        section payloads."""
        os.stat(self.path)
        if self.options.engine is not None:
            get_engine(self.options.engine)
        if self.format == FORMAT_GVEL:
            from . import codecs
            from .snapshot import SnapshotError
            entries = self._peek_gvel()[4]
            for sid, _code, _off, _nbytes, codec_id, _raw in entries:
                if codec_id:
                    try:                      # table metadata, not payload:
                        codecs.codec_for_id(codec_id)   # fail at open
                    except ValueError as exc:
                        raise SnapshotError(
                            f"{self.path}: section {sid}: {exc}") from None
        elif self.format == FORMAT_MTX:
            self._mtx_header()
        elif self._ckind == "framed":
            self._framed_info()

    def _peek_gvel(self):
        if self._gvel_peek is None:
            from .snapshot import peek_table
            self._gvel_peek = peek_table(self.path)
        return self._gvel_peek

    def _mtx_header(self):
        if self._mtx_hdr is None:
            from .mtx import read_header
            self._mtx_hdr = read_header(self.path)
        return self._mtx_hdr

    def _framed_info(self):
        if self._framed_hdr is None:
            from .codecs import read_framed_header
            self._framed_hdr = read_framed_header(self.path)
        return self._framed_hdr

    # -- option resolution ---------------------------------------------------

    def _weighted(self) -> bool:
        """Resolve ``weighted=None`` ("what the file says") once."""
        if self.options.weighted is not None:
            return self.options.weighted
        if self.format == FORMAT_GVEL:
            from .snapshot import FLAG_WEIGHTED
            return bool(self._peek_gvel()[1] & FLAG_WEIGHTED)
        if self.format == FORMAT_MTX:
            return self._mtx_header().meta.weighted
        return False                          # text has no header to ask

    def _opts_for(self, product: str) -> LoadOptions:
        engine = self.options.engine
        if engine is None:
            engine = (DEFAULT_EDGELIST_ENGINE if product == "edgelist"
                      else DEFAULT_CSR_ENGINE)
        return self.options.replace(engine=engine, weighted=self._weighted())

    # -- products ------------------------------------------------------------

    def info(self) -> SourceInfo:
        """Header-only metadata probe; memoized.  Reads the ``.gvel``
        header + section table, the MTX banner + size line, or the
        framed-container header — never a section payload and never a
        text parse."""
        if self._info is not None:
            return self._info
        size = os.path.getsize(self.path)
        codec = self._external_codec_name()
        version = v = e = None
        weighted = symmetric = has_el = has_csr = None
        section_frames = None
        raw = size if codec is None else None
        if self.format == FORMAT_GVEL:
            from . import codecs
            from .snapshot import (FLAG_CSR, FLAG_EDGELIST, FLAG_WEIGHTED,
                                   section_frame_counts)
            version, flags, v, e, entries = self._peek_gvel()
            weighted = bool(flags & FLAG_WEIGHTED)
            has_el = bool(flags & FLAG_EDGELIST)
            has_csr = bool(flags & FLAG_CSR)
            raw = sum(entry[5] for entry in entries)
            ids = {entry[4] for entry in entries} - {0}
            if ids:
                names = []
                for cid in sorted(ids):
                    try:
                        names.append(codecs.codec_for_id(cid).name)
                    except ValueError:
                        names.append(f"id{cid}")
                codec = "+".join(names)
                # frame counts per compressed section: a header walk
                # over the 12-byte frame headers (never a payload
                # decompression) — what the partial-decode planner sees
                section_frames = section_frame_counts(self.path)
        elif self.format == FORMAT_MTX:
            hdr = self._mtx_header()
            v, e = hdr.meta.num_vertices, hdr.meta.num_edges
            weighted, symmetric = hdr.meta.weighted, hdr.meta.symmetric
        if self._ckind == "framed":
            raw = self._framed_info().orig_len
        elif self._ckind == "gzip":
            from .codecs import gzip_length_hint
            try:
                raw = gzip_length_hint(self.path)
            except ValueError:
                raw = None
        self._info = SourceInfo(
            path=self.path, format=self.format, codec=codec,
            size_bytes=size, raw_bytes=raw, version=version,
            num_vertices=v, num_edges=e, weighted=weighted,
            symmetric=symmetric, has_edgelist=has_el, has_csr=has_csr,
            engine=self.options.engine, section_frames=section_frames)
        return self._info

    def _external_codec_name(self) -> Optional[str]:
        if self._ckind == "framed":
            return f"framed-{self._framed_info().codec.name}"
        return self._ckind                    # "gzip" or None

    def edgelist(self) -> EdgeList:
        """The graph as an :class:`EdgeList`; computed on first call,
        memoized on the handle."""
        if self._el is None:
            opts = self._opts_for("edgelist")
            with fault_plan(opts.faults):
                if self.format == FORMAT_MTX:
                    self._el = self._mtx_edgelist(opts)
                else:
                    self._el = read_edgelist_via(self.path, opts)
            self._el_engine = opts.engine
        return self._el

    def csr(self, *, rows=None) -> CSR:
        """The graph as a :class:`CSR`; computed on first call,
        memoized on the handle.  A ``.gvel`` snapshot with an embedded
        CSR serves it straight from mmap; every other source builds it
        with the staged build (``build.csr_staged``).

        ``rows`` selects a vertex-range slice: a ``range`` with step 1
        (or a ``(lo, hi)`` pair), returning a row-local CSR —
        ``offsets`` rebased to 0, ``row_start=lo``, global
        ``num_vertices`` — per the selective-read contract in
        ``docs/query.md``.  On a ``.gvel`` snapshot with an embedded
        CSR this is a *partial load*: raw sections are sliced straight
        off the mmap (no full-section copy) and compressed sections
        decode only the frames the row range's byte span touches.
        Other sources (text, MTX, edgelist-only snapshots) fall back to
        slicing the full — memoized — CSR, so the result is identical
        either way.  Row slices are not memoized (the full product is;
        slices are cheap and unbounded in number)."""
        if rows is not None:
            return self._csr_rows(rows)
        if self._csr is None:
            opts = self._opts_for("csr")
            if self.format == FORMAT_MTX:
                from .csr import convert_to_csr
                self._csr = convert_to_csr(
                    self.edgelist(), engine=csr_convert_engine(opts.engine))
            else:
                with fault_plan(opts.faults):
                    self._csr = read_csr_via(
                        self.path, opts,
                        fallback_edgelist=lambda: self._edgelist_for(opts))
        return self._csr

    def _selective_snap(self):
        """The pinned lazy :class:`Snapshot` when selective reads can
        serve this source: ``.gvel`` format, no symmetrize/offset
        transform, an embedded CSR, and any forced ``num_vertices``
        agreeing with the header — else ``None`` (callers fall back to
        slicing the full product).

        Pinned on the handle, not fetched through the snapshot engine's
        single-slot memo: the serving cache (:mod:`repro.core.cache`)
        keeps handles hot across a multi-snapshot corpus, and a point
        read is only decode-free on repeat if the partially-decoded
        frame cache survives with the handle."""
        if (self.format != FORMAT_GVEL or self.options.symmetric
                or self.options.offset):
            return None
        snap = self._snap
        if snap is None:
            from .snapshot import read_snapshot
            snap = self._snap = read_snapshot(self.path, eager=False)
        if not snap.has_csr:
            return None
        nv = self.options.num_vertices
        if nv is not None and int(nv) != snap.num_vertices:
            return None
        return snap

    def frame_cache_stats(self) -> Optional[dict]:
        """Decoded-frame memo counters of the pinned lazy snapshot
        handle (:meth:`repro.core.snapshot.Snapshot.frame_cache_stats`),
        or ``None`` when no snapshot is pinned — non-``.gvel`` sources,
        or a selective path never touched."""
        snap = self._snap
        return None if snap is None else snap.frame_cache_stats()

    def _csr_rows(self, rows) -> CSR:
        lo, hi = _normalize_rows(rows)
        snap = self._selective_snap()
        if snap is not None:
            return snap.csr_rows(lo, hi, weighted=self._weighted())
        return slice_csr(self.csr(), lo, hi)

    def neighbors(self, u: int, *, with_weights: bool = False):
        """Point lookup: vertex ``u``'s neighbor ids as a 1-D int32
        array (ids and weights as a pair with ``with_weights=True``).
        On a CSR-embedded ``.gvel`` snapshot this reads only the bytes
        vertex ``u``'s adjacency spans — two offsets plus the target
        run — decoding at most the frames that span touches; other
        sources fall back to slicing the full memoized CSR.  Not
        memoized (see ``docs/query.md``; the hot-graph cache in
        :mod:`repro.core.cache` is the serving layer's memo)."""
        u = int(u)
        if with_weights and not self._weighted():
            raise ValueError(
                f"{self.path}: with_weights=True but source is unweighted")
        snap = self._selective_snap()
        if snap is not None:
            # weights decode only when the caller asked for them
            return snap.neighbors(u, weighted=bool(with_weights))
        full = self.csr()
        if not 0 <= u < full.num_rows:
            raise IndexError(f"{self.path}: vertex {u} outside "
                             f"[0, {full.num_rows})")
        lo, hi = int(full.offsets[u]), int(full.offsets[u + 1])
        ids = np.asarray(full.targets)[lo:hi]
        if not with_weights:
            return ids
        return ids, np.asarray(full.weights)[lo:hi]

    def degree(self, u: int) -> int:
        """Vertex ``u``'s out-degree — on a CSR-embedded snapshot this
        touches exactly two offset elements."""
        u = int(u)
        snap = self._selective_snap()
        if snap is not None:
            return snap.degree(u)
        full = self.csr()
        if not 0 <= u < full.num_rows:
            raise IndexError(f"{self.path}: vertex {u} outside "
                             f"[0, {full.num_rows})")
        return int(full.offsets[u + 1]) - int(full.offsets[u])

    def csr_sharded(self, mesh, *, axis: str = "data") -> CSR:
        """The graph as a :class:`CSR` sharded row-wise across ``mesh``
        along ``axis``; computed on first call per ``(mesh, axis)``,
        memoized on the handle.

        Each mesh shard streams only its byte-range span of the file
        through the fused parse pipeline (:func:`repro.core.blocks.
        shard_plan` partitions the block plan; line ownership at span
        boundaries follows the terminating-newline rule, so no edge is
        parsed twice) and the packed per-shard device edges feed the
        distributed degree-psum / ``all_to_all`` / local-CSR build with
        no host detour.  ``offsets`` is the per-shard local offsets
        stacked along the mesh axis; see docs/distributed.md for the
        result layout.  Only text edgelists shard this way: MTX raises
        (banner semantics apply to :meth:`csr` only) and ``.gvel``
        snapshots raise (already parsed — no text to byte-partition).
        """
        if self.format == FORMAT_MTX:
            raise ValueError(
                f"{self.path}: csr_sharded() does not apply MTX banner "
                f"attributes; convert to a plain edgelist first or use "
                f".csr()")
        if self.format == FORMAT_GVEL:
            raise ValueError(
                f"{self.path}: .gvel snapshots are already parsed — "
                f"byte-range sharded streaming applies to text "
                f"edgelists; use .csr() and shard the result, or keep "
                f"the original text file for sharded loads")
        key = (mesh, axis)
        if key not in self._sharded_csrs:
            with fault_plan(self.options.faults):
                self._sharded_csrs[key] = read_csr_sharded_via(
                    self.path, self._opts_for("csr"), mesh=mesh, axis=axis)
        return self._sharded_csrs[key]

    def _edgelist_for(self, opts: LoadOptions) -> EdgeList:
        """EdgeList through a specific engine, sharing the memo when the
        engines coincide (always, when the caller pinned one engine at
        open).  Engines may differ in float rounding at the last ulp,
        so the CSR fallback never silently substitutes another
        engine's parse."""
        if self._el is not None and self._el_engine == opts.engine:
            return self._el
        el = read_edgelist_via(self.path, opts)
        if self._el is None:
            self._el, self._el_engine = el, opts.engine
        return el

    def _mtx_edgelist(self, opts: LoadOptions) -> EdgeList:
        from .mtx import read_mtx
        hdr = self._mtx_header()
        if opts.weighted and not hdr.meta.weighted:
            raise ValueError(
                f"{self.path}: weighted load requested but the MTX field "
                f"is 'pattern' (no weight column)")
        if (opts.num_vertices is not None
                and opts.num_vertices != hdr.meta.num_vertices):
            raise ValueError(
                f"{self.path}: num_vertices={opts.num_vertices} conflicts "
                f"with the MTX size line ({hdr.meta.num_vertices})")
        el = read_mtx(self.path, engine=opts.engine, **opts.engine_kw)
        if el.weights is not None and not opts.weighted:
            el = EdgeList(el.src, el.dst, None, el.num_edges, el.num_vertices)
        if opts.symmetric and not hdr.meta.symmetric:
            from .edgelist import symmetrize
            el = symmetrize(el)
        return el

    def stream(self, **kw):
        """Packed device edge buffers ``((src, dst, w, total), cap)``
        from a streaming-capable engine — the fused-build feed.  Not
        memoized (the buffers pin device memory).  Raises for host-only
        engines and for MTX (whose banner semantics — symmetry, field —
        only the EdgeList/CSR products apply)."""
        if self.format == FORMAT_MTX:
            raise ValueError(
                f"{self.path}: stream() does not apply MTX banner "
                f"attributes; use .edgelist() or .csr()")
        opts = resolve_tuned(self._opts_for("csr"))
        eng = get_engine(opts.engine)
        if not hasattr(eng, "stream"):
            raise ValueError(
                f"engine {opts.engine!r} has no stream fast path; "
                f"streaming engines: "
                f"{[n for n in available_engines() if hasattr(get_engine(n), 'stream')]}")
        with fault_plan(opts.faults):
            return eng.stream(self.path, **{**opts.stream_kwargs(), **kw})

    # -- write path ----------------------------------------------------------

    def save(self, out_path: str, *, compress: Optional[str] = None,
             compress_level: Optional[int] = None,
             csr: bool = True) -> "GraphSource":
        """Write this graph as a ``.gvel`` snapshot and return a handle
        on the output — the symmetric write path ("write once, load
        many").  ``compress`` accepts a codec spec (``"zlib"``,
        ``"zstd:9"``); ``csr=False`` stores only the packed edgelist.
        Products are reused: a memoized edgelist/CSR is not recomputed.
        """
        from .snapshot import SnapshotError, save_snapshot
        if compress is not None:
            from .codecs import parse_codec_spec
            codec, level = parse_codec_spec(compress)
            compress = codec.name
            if compress_level is None:
                compress_level = level
        if self.format == FORMAT_GVEL and not self.info().has_edgelist:
            if not csr:
                raise SnapshotError(
                    f"{self.path}: csr=False requested but this CSR-only "
                    f"snapshot has no edgelist sections to save")
            el, csr_obj = None, self.csr()    # CSR-only snapshots re-save
        else:
            el = self.edgelist()
            csr_obj = None
            if csr:
                if self.format == FORMAT_TEXT and self._csr is None:
                    # both products are needed: build the CSR from the
                    # edgelist just parsed instead of re-parsing the file
                    # on the streaming fast path (one parse per save)
                    from .csr import convert_to_csr
                    opts = self._opts_for("csr")
                    self._csr = convert_to_csr(
                        el, engine=csr_convert_engine(opts.engine))
                csr_obj = self.csr()
        save_snapshot(out_path, edgelist=el, csr=csr_obj, compress=compress,
                      compress_level=compress_level)
        return GraphSource(out_path, LoadOptions(), validate=True)


def open_graph(
    path: str,
    *,
    engine: Optional[str] = None,
    weighted: Optional[bool] = None,
    base: Optional[int] = None,
    offset: int = 0,
    validate: bool = True,
    symmetric: bool = False,
    num_vertices: Optional[int] = None,
    tune: bool = False,
    faults: Optional[Any] = None,
    **engine_kw,
) -> GraphSource:
    """Open a graph file as a lazy :class:`GraphSource` handle.

    Format (``.gvel`` / MTX / text) and compression (gzip / framed) are
    sniffed by magic once, here.  ``engine=None`` picks the per-product
    default (``numpy`` for edgelists, ``device`` for fused CSR builds;
    ``.gvel`` files always route to the snapshot engine).
    ``weighted=None`` means "what the file says" (snapshot flags / MTX
    banner; text resolves to False).  ``base=None`` defaults to the
    1-based text convention (snapshots are canonical 0-based and ignore
    it).  ``validate=True`` runs cheap structural checks at open —
    existence, container headers, engine name — but never touches
    section payloads; ``validate=False`` defers even those to first
    access (useful for paths only a custom engine knows how to read).
    ``engine_kw`` carries engine tuning knobs (``beta``,
    ``batch_blocks``, ``num_workers``, ...).  ``tune=True`` fills
    un-pinned streaming block geometry from the measured per-host
    profile (:mod:`repro.core.tune`; first use on a host runs the
    sweep and caches it — see docs/performance.md).  ``faults`` pins a
    :class:`repro.core.faults.FaultPlan` on the handle — every product
    load runs under that plan (see docs/robustness.md).
    """
    opts = LoadOptions(engine=engine, weighted=weighted, symmetric=symmetric,
                       base=1 if base is None else base,
                       num_vertices=num_vertices, offset=offset, tune=tune,
                       faults=faults, engine_kw=dict(engine_kw))
    with span("load.open"):
        return GraphSource(path, opts, validate=validate)


def _main(argv: Optional[list] = None) -> int:
    """``python -m repro.core.source <path> [path ...]`` — print
    ``info()`` for each path as JSON (one object, or a list)."""
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser(
        prog="python -m repro.core.source",
        description="Probe graph files: print GraphSource.info() as JSON")
    ap.add_argument("paths", nargs="+", help="graph files (.el/.mtx/.gvel, "
                    "raw or compressed)")
    args = ap.parse_args(argv)
    out, failed = [], False
    for p in args.paths:
        try:
            out.append(open_graph(p).info().to_dict())
        except (OSError, ValueError) as exc:
            out.append({"path": p, "error": str(exc)})
            failed = True
    print(json.dumps(out[0] if len(out) == 1 else out, indent=2))
    if failed:
        print("probe failed for one or more paths", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    import sys
    sys.exit(_main())
