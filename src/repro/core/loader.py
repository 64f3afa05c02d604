"""Unified streaming loader: the engine registry and engine-call layer.

The user-facing front door is :mod:`repro.core.source` —
``open_graph(path) -> GraphSource`` — which resolves format/codec/
engine once and serves lazy, memoized products.  This module keeps the
layer underneath it: the engine registry, the normalized
:class:`LoadOptions` every engine call is expanded from, the streaming
pipeline, and the historical ``load_edgelist`` (file -> EdgeList) /
``load_csr`` (file -> CSR) wrappers, with the parse backend selected by
name from the registry:

    ==========  ================================================
    engine      implementation
    ==========  ================================================
    device      streaming double-buffered block pipeline ->
                jitted ``parse_blocks`` -> packed device buffers
    pallas      same pipeline, but parsing runs through
                ``kernels.parse_edges.parse_edges_accumulate``
                (its XLA twin; refused on a TPU backend, where
                the kernel does not lower yet)
    numpy       single-pass vectorized numpy parser (host)
    threads     thread pool over newline-aligned chunks (host)
    snapshot    zero-parse mmap of a binary ``.gvel`` snapshot
                (``core.snapshot``; write once, load many)
    ==========  ================================================

The device/pallas engines are *streaming* (GVEL's pipelined read):

  1. a host prefetch thread stages the next batch of overlap-padded
     byte blocks (``blocks.stage_blocks``, through a reusable
     :class:`~repro.core.blocks.StagingArena` — no per-batch
     allocation) while the device parses the current one — read IO and
     parse compute overlap, the madvise / double-buffer effect the
     paper measures;
  2. each batch runs ONE jitted program (``parse.parse_accumulate``)
     that parses the blocks and writes the edges straight into packed
     device accumulators at the running offset, with the accumulator
     buffers *donated* so the update is in-place — per-block parse
     outputs never materialize between programs and the capacity-sized
     buffers are not copied per batch (the pallas engine runs the same
     fused-donated shape through ``kernels.parse_edges``);
  3. the final short batch runs a remainder-sized program instead of
     being padded with ``NEWLINE`` blocks to ``batch_blocks`` — small
     inputs don't pay full-batch parse cost for padding;
  4. ``load_csr`` hands the packed device buffers straight to the
     rank-based CSR builders (``build.csr_global``/``csr_staged``/
     ``csr_binned``), so file -> CSR never materializes a host-side
     EdgeList.

Block geometry (``beta`` x ``batch_blocks``) defaults to
``DEFAULT_BETA``/``DEFAULT_BATCH_BLOCKS`` and can be *measured* instead:
``tune=True`` (via ``LoadOptions`` / ``open_graph``) fills un-pinned
geometry from the per-host profile in :mod:`repro.core.tune` (a GVEL
Fig. 2 style sweep, run once and cached).  See docs/performance.md.

Compressed inputs are transparent at every entry point: gzip and
framed files (``core.codecs``) are sniffed by magic, streamed through
the same double-buffered pipeline with decompression in the prefetch
thread, and handed decompressed to the host engines.  New formats or
backends register with :func:`register_engine`; the registry is the
extension point for new loaders (see ROADMAP.md "Open items").

Engine contract: ``read_edgelist`` must return the raw (asymmetric)
edge set; symmetrization happens once, in the front door.
"""
from __future__ import annotations

import dataclasses
import functools
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FutTimeout
from typing import (Any, Callable, Dict, Optional, Protocol, Tuple,
                    runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np

from . import build
from . import faults
from . import parse as parse_mod
from .blocks import StagingArena, flat_len, owned_range, plan_blocks
from .parse import donation_supported, parse_accumulate
from .types import CSR, EdgeList

I32 = jnp.int32

# the per-product engine defaults the wrappers have always used: host
# EdgeLists parse fastest on the numpy engine; CSR builds run fused on
# the streaming device engine
DEFAULT_EDGELIST_ENGINE = "numpy"
DEFAULT_CSR_ENGINE = "device"

# fallback streaming block geometry (GVEL's paper values), used when the
# caller pins nothing and tuning is off; `tune=True` replaces them with
# the measured per-host profile (core.tune)
DEFAULT_BETA = 256 * 1024
DEFAULT_BATCH_BLOCKS = 8
DEFAULT_OVERLAP = 64


@dataclasses.dataclass(frozen=True)
class LoadOptions:
    """The normalized loading knobs, consolidated from the kwargs that
    used to be scattered across every ``load_*``/``read_*`` signature.

    One instance travels from the front door (:func:`repro.core.source.
    open_graph` / a ``GraphSource``) down to every engine call — the
    expansion helpers below are the *only* place option names map onto
    engine-call keywords, so an engine can never see a half-normalized
    set.

    ``engine=None`` means "per-product default" (``numpy`` for
    edgelists, ``device`` for CSRs); ``weighted=None`` means "what the
    file says" (snapshot flags / MTX banner; plain text has no header,
    so it resolves to False).  ``engine_kw`` carries engine tuning
    knobs (``beta``, ``batch_blocks``, ``num_workers``, ...) verbatim.
    ``tune=True`` fills un-pinned streaming block geometry from the
    measured per-host profile (:mod:`repro.core.tune`); explicit
    ``engine_kw`` values always win, and non-streaming engines ignore
    it.

    ``method``/``bin_bits`` pick the CSR build strategy for every
    ``.csr()``-family product off this handle (``method=None`` means the
    per-call default, ``staged``); a per-call ``method=`` always wins.
    ``bin_bits`` is the binned build's vertex-range width knob and is
    ignored by the sort-based methods.

    ``faults`` pins a :class:`repro.core.faults.FaultPlan` on the
    handle: every product call runs under that plan (chaos testing a
    single source without touching the process-wide plan).  Never
    expanded into engine kwargs.
    """

    engine: Optional[str] = None
    weighted: Optional[bool] = None
    symmetric: bool = False
    base: int = 1
    num_vertices: Optional[int] = None
    offset: int = 0
    tune: bool = False
    method: Optional[str] = None
    bin_bits: Optional[int] = None
    faults: Optional[Any] = None
    engine_kw: Dict[str, Any] = dataclasses.field(default_factory=dict)

    _OWN_FIELDS = ("engine", "weighted", "symmetric", "base",
                   "num_vertices", "offset", "tune", "method", "bin_bits",
                   "faults")

    def __post_init__(self):
        if self.base not in (0, 1):
            raise ValueError(f"base must be 0 or 1, got {self.base!r}")
        if self.offset < 0:
            raise ValueError(f"offset must be >= 0, got {self.offset!r}")
        if self.method not in (None, "global", "staged", "binned"):
            raise ValueError(f"unknown method {self.method!r}; expected "
                             f"'global', 'staged' or 'binned'")
        dup = sorted(set(self.engine_kw) & set(self._OWN_FIELDS))
        if dup:
            raise ValueError(f"option(s) {dup} passed both named and via "
                             f"engine_kw")

    def replace(self, **changes) -> "LoadOptions":
        return dataclasses.replace(self, **changes)

    def read_kwargs(self) -> Dict[str, Any]:
        """Keywords for an engine's ``read_edgelist``."""
        return dict(self.engine_kw, weighted=bool(self.weighted),
                    base=self.base, num_vertices=self.num_vertices,
                    offset=self.offset)

    def stream_kwargs(self) -> Dict[str, Any]:
        """Keywords for an engine's ``stream`` (no ``num_vertices`` —
        streams infer or take the front door's hint)."""
        return dict(self.engine_kw, weighted=bool(self.weighted),
                    base=self.base, offset=self.offset)

    def prebuilt_kwargs(self) -> Dict[str, Any]:
        """Keywords for an engine's ``read_csr_prebuilt``."""
        return dict(self.engine_kw, weighted=bool(self.weighted),
                    num_vertices=self.num_vertices, offset=self.offset)

# (src, dst, weights-or-None, num_edges device scalar) — packed device
# buffers with -1 padding past num_edges; the streaming engines' output.
DeviceEdges = Tuple[jax.Array, jax.Array, Optional[jax.Array], jax.Array]


@runtime_checkable
class LoaderEngine(Protocol):
    """A parse backend. ``read_edgelist`` is mandatory; engines that can
    leave edges on device additionally implement ``stream`` (the fused
    ``load_csr`` path probes for it with ``hasattr``)."""

    name: str

    def read_edgelist(self, path: str, *, weighted: bool, base: int,
                      num_vertices: Optional[int], offset: int,
                      **kw) -> EdgeList: ...


_REGISTRY: Dict[str, "LoaderEngine"] = {}


def register_engine(engine: LoaderEngine) -> LoaderEngine:
    """Register an engine instance under ``engine.name`` (last wins)."""
    _REGISTRY[engine.name] = engine
    return engine


def get_engine(name: str) -> LoaderEngine:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown loader engine {name!r}; available: {available_engines()}"
        ) from None


def available_engines() -> list[str]:
    return sorted(_REGISTRY)


def csr_convert_engine(engine: str) -> str:
    """Map a loader engine name to a ``convert_to_csr`` backend: host
    parsers keep the numpy builder, everything else builds on device."""
    return "numpy" if engine in ("numpy", "threads") else "jax"


# ---------------------------------------------------------------------------
# streaming device pipeline
# ---------------------------------------------------------------------------

def _accumulate_impl(acc_src, acc_dst, acc_w, total, src_b, dst_b, w_b,
                     counts, *, cap: int):
    nb, bcap = src_b.shape
    starts = total + jnp.cumsum(counts) - counts
    within = jnp.arange(bcap, dtype=I32)[None, :]
    valid = within < counts[:, None]
    dest = jnp.where(valid, starts[:, None] + within, cap).reshape(-1)
    acc_src = acc_src.at[dest].set(src_b.reshape(-1), mode="drop")
    acc_dst = acc_dst.at[dest].set(dst_b.reshape(-1), mode="drop")
    if acc_w is not None and w_b is not None:
        acc_w = acc_w.at[dest].set(w_b.reshape(-1), mode="drop")
    return acc_src, acc_dst, acc_w, total + jnp.sum(counts, dtype=I32)


@functools.lru_cache(maxsize=None)
def _accumulate_jit(donate: bool):
    return jax.jit(_accumulate_impl, static_argnames=("cap",),
                   donate_argnums=(0, 1, 2) if donate else ())


def _accumulate_batch(acc_src, acc_dst, acc_w, total, src_b, dst_b, w_b,
                      counts, *, cap: int, donate: Optional[bool] = None):
    """Scatter one batch of per-block fixed-capacity parses into the
    packed accumulator at the running offset.

    The device-side analogue of gluing per-thread edgelists: an exclusive
    scan over per-block counts gives each block a disjoint destination
    range starting at ``total``.  Replaces the old per-batch
    device->numpy copy + final np.concatenate.  Kept as the two-step
    reference pipeline (the fused-loader parity tests pin it); both
    streaming engines now run fused —
    :func:`repro.core.parse.parse_accumulate` for ``device``,
    ``kernels.parse_edges.parse_edges_accumulate`` for ``pallas``.

    ``donate=None`` probes the backend once and donates the accumulator
    buffers when supported, making the scatter in-place instead of
    copying the capacity-sized buffers every batch.  Donated inputs are
    consumed — rebind, never reuse, the passed accumulators.
    ``donate=False`` is the fallback for backends that refuse donation.
    """
    if donate is None:
        donate = donation_supported()
    return _accumulate_jit(bool(donate))(
        acc_src, acc_dst, acc_w, total, src_b, dst_b, w_b, counts, cap=cap)


def _guard_int32_cap(path: str, cap: int) -> None:
    """Scatter destinations are int32 (jax default dtype regime); a
    wrapped index would silently drop edges via mode="drop", so refuse
    loudly instead."""
    if cap > np.iinfo(np.int32).max:
        raise ValueError(
            f"{path}: edge capacity {cap} exceeds int32 indexing for the "
            f"streaming engine; use engine='numpy'/'threads' or shard the "
            f"file (load_csr_sharded)")


def _parse_span(
    source,
    plan,
    block_lo: int,
    block_hi: int,
    *,
    weighted: bool,
    base: int,
    batch_blocks: int,
    parse: str,
    cap: int,
    device=None,
    prefetch: bool = True,
) -> DeviceEdges:
    """Stage and fused-parse blocks ``[block_lo, block_hi)`` of ``plan``
    from ``source`` into fresh packed accumulators of ``cap`` slots.

    The single-span streaming loop shared by :func:`_stream_edges`
    (whole file, ``prefetch=True``) and the sharded loader
    (:mod:`repro.core.distributed`, one call per mesh shard's byte
    range).  ``device`` commits the accumulators — and every staged
    batch — to one device, so the donated parse chain executes there;
    ``prefetch=False`` stages inline instead of spawning a prefetch
    thread (the sharded loader's callers *are* per-shard threads:
    inline staging of batch i+1 already overlaps the async-dispatched
    device parse of batch i, without d extra threads).
    """
    if parse == "pallas" and jax.default_backend() == "tpu":
        raise NotImplementedError(
            "engine 'pallas': the kernels.parse_edges Pallas kernel does "
            "not lower for TPU yet (Mosaic refuses its (1, buf_len) block "
            "shape and has no cumsum lowering); use engine='device'")
    os_, oe = owned_range(plan)
    edge_cap = plan.edge_cap
    nspan = max(block_hi - block_lo, 0)
    num_batches = -(-nspan // batch_blocks)
    acc_src, acc_dst, acc_w, total = parse_mod.make_accumulators(
        cap, weighted=weighted, device=device)
    if num_batches == 0:
        return acc_src, acc_dst, acc_w, total

    def put(x):
        return jnp.asarray(x) if device is None else jax.device_put(x, device)

    arena = StagingArena(flat_len(min(batch_blocks, nspan), plan))
    where = getattr(source, "_describe", None) or "block source"

    def batch_bytes(i: int) -> Tuple[int, int]:
        """Post-offset byte span batch ``i`` stages (for error text)."""
        start = block_lo + i * batch_blocks
        stop = min(start + batch_blocks, block_hi)
        return start * plan.beta, min(stop * plan.beta, plan.file_len)

    def stage(i: int) -> np.ndarray:
        start = block_lo + i * batch_blocks
        ids = np.arange(start, min(start + batch_blocks, block_hi))
        # retries are safe here: injected faults fire before the source
        # cursor moves, and raw (mmap) staging is idempotent.  A retry
        # that still fails escalates to the shard/load level, where
        # re-execution reopens the source from scratch.
        return faults.call_with_retries(
            lambda: source.stage(plan, ids, arena=arena, check_lines=True),
            describe=f"{where}: stage blocks "
                     f"[{int(ids[0])}, {int(ids[-1]) + 1})")

    ostart = put(np.full((batch_blocks,), os_, np.int32))
    oend = put(np.full((batch_blocks,), oe, np.int32))

    def consume(i: int, bufs: np.ndarray) -> None:
        nonlocal acc_src, acc_dst, acc_w, total
        nb = bufs.shape[0]          # < batch_blocks on the tail batch
        if parse == "pallas":
            from ..kernels import parse_edges_accumulate
            acc_src, acc_dst, acc_w, total = parse_edges_accumulate(
                acc_src, acc_dst, acc_w, total, put(bufs), os_, oe,
                weighted=weighted, base=base, edge_bound=nb * edge_cap)
        else:
            acc_src, acc_dst, acc_w, total = parse_accumulate(
                acc_src, acc_dst, acc_w, total, put(bufs),
                ostart[:nb], oend[:nb], weighted=weighted, base=base,
                edge_bound=nb * edge_cap)

    if prefetch:
        # not a with-block: a stuck staging thread must be *abandoned*
        # (shutdown(wait=False)), never joined — joining would turn the
        # watchdog timeout back into the hang it exists to prevent
        pool = ThreadPoolExecutor(1, thread_name_prefix="loader-prefetch")
        try:
            fut = pool.submit(stage, 0)
            for i in range(num_batches):
                try:
                    bufs = fut.result(timeout=faults.WATCHDOG_S)
                except _FutTimeout:
                    faults._count("stage_timeouts")
                    lo_b, hi_b = batch_bytes(i)
                    raise faults.StageTimeout(
                        f"{where}: staging of byte span [{lo_b}, {hi_b}) "
                        f"(batch {i + 1}/{num_batches}) produced nothing "
                        f"within the {faults.WATCHDOG_S:.1f}s watchdog "
                        f"budget (REPRO_WATCHDOG_S); reader is stuck"
                    ) from None
                if i + 1 < num_batches:
                    fut = pool.submit(stage, i + 1)     # double buffer
                consume(i, bufs)
        finally:
            pool.shutdown(wait=False, cancel_futures=True)
    else:
        for i in range(num_batches):
            consume(i, stage(i))
    return acc_src, acc_dst, acc_w, total


def _stream_edges(
    path: str,
    *,
    weighted: bool,
    base: int,
    offset: int,
    beta: int,
    overlap: int,
    batch_blocks: int,
    parse: str,
) -> Tuple[DeviceEdges, int]:
    """File -> packed device edge buffers, double-buffered.

    Returns ((src, dst, w, total), capacity).  The prefetch thread stages
    batch i+1 (into a reusable :class:`StagingArena` ring — one memcpy
    per batch, no allocation) while the (async-dispatched) fused
    parse+accumulate program works on batch i, so host staging overlaps
    device compute.  The final short batch is *not* padded to
    ``batch_blocks``: it runs a second, remainder-sized program, so a
    2-block file parses 2 blocks, not ``batch_blocks``.

    Compressed inputs (``.el.gz`` / framed — sniffed by magic in
    :func:`codecs.open_block_source`) ride the same pipeline: the block
    source decompresses inside ``stage``, i.e. in the prefetch thread,
    so decompression overlaps the device parse exactly like raw-file IO
    does.  Framed files force ``beta`` to the file's frame size so
    frames map 1:1 onto staging blocks.

    Lines longer than ``overlap`` bytes that cross a block boundary are
    detected during staging and raise ``ValueError``
    (:func:`repro.core.blocks.check_line_overlap`) instead of silently
    mis-parsing.
    """
    from .codecs import open_block_source
    source, forced_beta = open_block_source(path, offset)
    if forced_beta is not None and forced_beta > overlap:
        beta = forced_beta
    plan = plan_blocks(source.length, beta=beta, overlap=overlap)
    # GVEL over-allocation: a bytes-derived bound on the final edge count
    # (~file_len/4 slots).  This trades device memory (~1 int32 per file
    # byte across src+dst) for a single allocation and in-place (donated)
    # accumulation; load_csr shrinks to a pow-2 prefix before sorting.
    # Growable buffers for accelerator-memory-bound inputs are an open
    # item (ROADMAP.md).  Because batches are trimmed (never padded), the
    # per-batch windows tile [0, cap) exactly and the running offset can
    # never push a window past the end.
    cap = plan.num_blocks * plan.edge_cap
    _guard_int32_cap(path, cap)
    edges = _parse_span(source, plan, 0, plan.num_blocks, weighted=weighted,
                        base=base, batch_blocks=batch_blocks, parse=parse,
                        cap=cap)
    # A stream shorter/longer than its header declared (truncated file,
    # lying gzip trailer) must fail here, not return a partial graph.
    source.finish()
    return edges, cap


def _device_num_vertices(src: jax.Array, dst: jax.Array) -> int:
    """max id + 1 over the packed buffers (-1 padding never wins)."""
    return int(jnp.maximum(jnp.max(src, initial=-1),
                           jnp.max(dst, initial=-1))) + 1


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------

class _StreamingEngine:
    """Shared streaming pipeline; ``parse`` picks the block parser."""

    def __init__(self, name: str, parse: str):
        self.name = name
        self._parse = parse

    def stream(self, path: str, *, weighted: bool = False, base: int = 1,
               offset: int = 0, beta: Optional[int] = None,
               overlap: Optional[int] = None,
               batch_blocks: Optional[int] = None
               ) -> Tuple[DeviceEdges, int]:
        return _stream_edges(
            path, weighted=weighted, base=base, offset=offset,
            beta=DEFAULT_BETA if beta is None else beta,
            overlap=DEFAULT_OVERLAP if overlap is None else overlap,
            batch_blocks=(DEFAULT_BATCH_BLOCKS if batch_blocks is None
                          else batch_blocks),
            parse=self._parse)

    def read_edgelist(self, path: str, *, weighted: bool = False,
                      base: int = 1, num_vertices: Optional[int] = None,
                      offset: int = 0, **kw) -> EdgeList:
        (src, dst, w, total), _ = self.stream(
            path, weighted=weighted, base=base, offset=offset, **kw)
        n = int(total)
        src_h = np.asarray(src[:n])
        dst_h = np.asarray(dst[:n])
        w_h = np.asarray(w[:n]) if weighted else None
        if num_vertices is None:
            num_vertices = int(max(src_h.max(initial=-1),
                                   dst_h.max(initial=-1))) + 1
        return EdgeList(src_h, dst_h, w_h, np.int64(n), num_vertices)


class _HostEngine:
    """Adapter around the host parsers in :mod:`repro.core.edgelist`."""

    def __init__(self, name: str, fn: Callable):
        self.name = name
        self._fn = fn

    def read_edgelist(self, path: str, *, weighted: bool = False,
                      base: int = 1, num_vertices: Optional[int] = None,
                      offset: int = 0, **kw) -> EdgeList:
        return self._fn(path, weighted=weighted, base=base,
                        num_vertices=num_vertices, offset=offset, **kw)


def _register_builtin_engines() -> None:
    from . import edgelist, snapshot
    register_engine(_StreamingEngine("device", parse="xla"))
    register_engine(_StreamingEngine("pallas", parse="pallas"))
    register_engine(_HostEngine("numpy", edgelist.read_edgelist_numpy))
    register_engine(_HostEngine("threads", edgelist.read_edgelist_threads))
    register_engine(snapshot.SnapshotEngine())


# ---------------------------------------------------------------------------
# engine-call implementations (shared by GraphSource and the wrappers)
# ---------------------------------------------------------------------------

def resolve_tuned(opts: LoadOptions, *, shards: int = 1) -> LoadOptions:
    """Fill un-pinned streaming block geometry from the measured
    per-host profile when ``opts.tune`` is set.

    Only streaming engines have ``beta``/``batch_blocks`` geometry;
    tuning is a no-op for host/snapshot engines.  Explicit ``engine_kw``
    values always win over the profile (pin one, tune the other).  The
    first tuned load on a host runs the measurement sweep and caches it
    (:func:`repro.core.tune.tuned_geometry`).  ``shards`` selects the
    per-shard-count profile slot for the sharded streaming path — d
    concurrent parse pipelines over 1/d of the bytes have a different
    throughput knee than one pipeline over all of them.
    """
    if not opts.tune or not isinstance(_REGISTRY.get(opts.engine),
                                       _StreamingEngine):
        return opts
    kw = dict(opts.engine_kw)
    if "beta" in kw and "batch_blocks" in kw:
        return opts
    from .tune import tuned_geometry
    g = tuned_geometry(weighted=bool(opts.weighted), shards=int(shards))
    kw.setdefault("beta", g["beta"])
    kw.setdefault("batch_blocks", g["batch_blocks"])
    return opts.replace(engine_kw=kw)


def read_edgelist_via(path: str, opts: LoadOptions) -> EdgeList:
    """File -> EdgeList through ``opts.engine`` (must be concrete).
    Symmetrization happens here, once — engines return the raw edge
    set (the engine contract, docs/extending.md)."""
    opts = resolve_tuned(opts)
    el = get_engine(opts.engine).read_edgelist(path, **opts.read_kwargs())
    if opts.symmetric:
        from .edgelist import symmetrize
        el = symmetrize(el)
    return el


def read_csr_via(path: str, opts: LoadOptions, *,
                 method: Optional[str] = None, rho: int = 4,
                 bin_bits: Optional[int] = None,
                 fallback_edgelist: Optional[Callable[[], EdgeList]] = None,
                 ) -> CSR:
    """File -> CSR through ``opts.engine`` (must be concrete).

    Probes the engine's optional fast paths in speedup order:
    ``read_csr_prebuilt`` (no parse, no build), then ``stream`` (fused
    device build, no host EdgeList), then the EdgeList + convert route.
    ``fallback_edgelist`` lets a :class:`~repro.core.source.GraphSource`
    feed its memoized edgelist into that last route instead of
    re-reading the file.  Symmetric graphs always take the EdgeList
    route (reverse-edge expansion is a host concatenation today).
    ``method=None`` falls back to ``opts.method``, then ``staged``.
    """
    opts = resolve_tuned(opts)
    method = method or opts.method or "staged"
    bin_bits = bin_bits if bin_bits is not None else opts.bin_bits
    weighted = bool(opts.weighted)
    eng = get_engine(opts.engine)
    if hasattr(eng, "read_csr_prebuilt") and not opts.symmetric:
        csr = eng.read_csr_prebuilt(path, **opts.prebuilt_kwargs())
        if csr is not None:
            return csr
    if hasattr(eng, "stream") and not opts.symmetric:
        num_vertices = opts.num_vertices
        if num_vertices is None and hasattr(eng, "num_vertices_hint"):
            num_vertices = eng.num_vertices_hint(path)
        (src, dst, w, total), _cap = eng.stream(path, **opts.stream_kwargs())
        n = int(total)
        if num_vertices is None:
            num_vertices = _device_num_vertices(src, dst) if n else 0
        # Shrink the over-allocated buffers to the next power of two >= n
        # before sorting: padding is all at the tail, so a prefix slice
        # keeps every valid edge while bounding the sort size at 2n (and
        # the pow-2 ladder bounds recompiles at log2(capacity) programs).
        cap2 = 1 << max(n - 1, 1).bit_length()
        if cap2 < src.shape[0]:
            src, dst = src[:cap2], dst[:cap2]
            w = w[:cap2] if weighted else None
        if method == "global":
            offsets, targets, ww = build.csr_global(
                src, dst, w, num_vertices, weighted=weighted)
        elif method == "staged":
            offsets, targets, ww = build.csr_staged(
                src, dst, w, num_vertices, rho=rho, weighted=weighted)
        elif method == "binned":
            offsets, targets, ww = build.csr_binned(
                src, dst, w, num_vertices, bin_bits=bin_bits,
                weighted=weighted)
        else:
            raise ValueError(f"unknown method {method!r}")
        return CSR(np.asarray(offsets).astype(np.int64),
                   np.asarray(targets[:n]),
                   np.asarray(ww[:n]) if weighted else None,
                   num_vertices)
    from .csr import convert_to_csr
    el = (fallback_edgelist() if fallback_edgelist is not None
          else read_edgelist_via(path, opts))
    return convert_to_csr(el, method=method, rho=rho, bin_bits=bin_bits,
                          engine=csr_convert_engine(opts.engine))


def read_csr_sharded_via(path: str, opts: LoadOptions, *, mesh,
                         axis: str = "data", rho: int = 4,
                         method: Optional[str] = None,
                         bin_bits: Optional[int] = None) -> CSR:
    """File -> mesh-sharded CSR through ``opts.engine`` (must be a
    streaming engine — the byte-range shard plan only exists for the
    block streaming pipeline).

    Expands ``LoadOptions`` onto :func:`repro.core.distributed.
    load_csr_sharded_stream`: each mesh shard along ``axis`` streams its
    own byte span of the file through the fused parse pipeline and the
    packed per-shard edges feed the degree-psum / all_to_all / local
    CSR build with no host detour.  ``tune=True`` resolves against the
    per-shard-count profile slot.
    """
    if axis not in dict(getattr(mesh, "shape", {})):
        raise ValueError(f"mesh has no axis {axis!r} "
                         f"(axes: {tuple(dict(mesh.shape))})")
    opts = resolve_tuned(opts, shards=int(mesh.shape[axis]))
    if opts.symmetric:
        raise ValueError(
            "sharded streaming load does not support symmetric=True "
            "(reverse-edge expansion is a host concatenation; load the "
            "CSR unsharded or pre-symmetrize the file)")
    eng = get_engine(opts.engine)
    if not isinstance(eng, _StreamingEngine):
        raise ValueError(
            f"engine {opts.engine!r} has no sharded streaming path; use a "
            f"streaming engine ('device' or 'pallas')")
    from . import distributed
    return distributed.load_csr_sharded_stream(
        mesh, axis, path, num_vertices=opts.num_vertices, rho=rho,
        method=method or opts.method or "staged",
        bin_bits=bin_bits if bin_bits is not None else opts.bin_bits,
        parse=eng._parse, **opts.stream_kwargs())


# ---------------------------------------------------------------------------
# front door (thin wrappers over repro.core.source.open_graph)
# ---------------------------------------------------------------------------

def load_edgelist(
    path: str,
    *,
    engine: str = DEFAULT_EDGELIST_ENGINE,
    weighted: bool = False,
    symmetric: bool = False,
    base: int = 1,
    num_vertices: Optional[int] = None,
    offset: int = 0,
    tune: bool = False,
    **engine_kw,
) -> EdgeList:
    """File -> EdgeList through the named engine.

    A thin wrapper over the :class:`~repro.core.source.GraphSource`
    front door — equivalent to ``open_graph(path, ...).edgelist()``.
    ``offset`` skips a header prefix (MTX bodies); ``engine_kw`` is
    forwarded to the engine (beta/batch_blocks for device, num_workers
    for threads, chunk_bytes for numpy, ...); ``tune=True`` fills
    un-pinned streaming geometry from the measured per-host profile.
    Binary ``.gvel`` files are detected by magic and routed to the
    snapshot engine.
    """
    from .source import open_graph
    return open_graph(path, engine=engine, weighted=weighted,
                      symmetric=symmetric, base=base,
                      num_vertices=num_vertices, offset=offset, tune=tune,
                      validate=False, **engine_kw).edgelist()


def load_csr(
    path: str,
    *,
    engine: str = DEFAULT_CSR_ENGINE,
    weighted: bool = False,
    symmetric: bool = False,
    base: int = 1,
    num_vertices: Optional[int] = None,
    method: str = "staged",
    rho: int = 4,
    bin_bits: Optional[int] = None,
    offset: int = 0,
    tune: bool = False,
    **engine_kw,
) -> CSR:
    """File -> CSR through the named engine.

    A thin wrapper over the :class:`~repro.core.source.GraphSource`
    front door — equivalent to ``open_graph(path, ...).csr(...)``.
    Streaming engines (device, pallas) run fused: one jitted program
    per batch parses the blocks and accumulates the edges in packed
    (donated) device buffers that feed the rank-based builders
    (``csr_global``/``csr_staged``/``csr_binned``) directly — no host
    EdgeList in between.  ``tune=True`` fills un-pinned streaming
    geometry from the measured per-host profile.  Host engines read an
    EdgeList and convert.  Binary ``.gvel`` files are detected by magic
    and routed to the snapshot engine; an embedded prebuilt CSR is
    served straight from mmap (``method``/``rho``/``bin_bits`` do not
    apply — the stored CSR wins).
    """
    from .source import open_graph
    return open_graph(path, engine=engine, weighted=weighted,
                      symmetric=symmetric, base=base,
                      num_vertices=num_vertices, offset=offset, tune=tune,
                      validate=False, **engine_kw).csr(method=method, rho=rho,
                                                       bin_bits=bin_bits)


_register_builtin_engines()
