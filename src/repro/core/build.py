"""Edgelist -> CSR construction strategies (GVEL §4.2.3-4.2.4, TPU-adapted).

There is no fetch-add on TPU, so PIGO's "claim a slot atomically" becomes a
deterministic *rank*: edge e with source u lands at offsets[u] + (rank of e
among u's edges).  Ranks come from a stable sort (and, in ``csr_staged``,
the exclusive scan of the degree histogram), which makes construction a
pure gather/scatter with provably disjoint destinations.

* ``csr_global``    — one global stable sort over all edges
                      (single-stage; the PIGO-shaped baseline).
* ``csr_staged``    — GVEL's multi-stage build: edges are cut into rho
                      contiguous partitions; each partition sorts locally
                      (smaller sorts, independent -> parallel across cores
                      or devices) and is merged into the global CSR through
                      per-partition base offsets.  Stage-local work is
                      contention-free; only the merge touches shared state,
                      and its destinations are disjoint by construction.
* ``csr_binned``    — propagation-blocking-style binned build: vertices are
                      cut into contiguous ranges ("bins") of 2**bin_bits,
                      and edges are grouped one bin digit per level with the
                      cumulative-count algebra from the PR-5/PR-6 parse and
                      exchange paths — no argsort, no comparator sort with
                      payloads, and no scatters at all.  Each level packs
                      (digit << pos_bits) | position into one int32 and
                      value-sorts it (XLA's single-operand fast path, ~5x
                      the throughput of the comparator argsort on CPU); the
                      low bits of the sorted keys ARE the level permutation,
                      so composing levels and filling targets/weights is
                      pure gathers whose destinations are disjoint by
                      construction.  Offsets come from one degree histogram
                      + cumsum.  ~2x over ``csr_staged`` on the CI host.

Fixed-capacity buffers use src == -1 as padding; padding sorts to the end
(key |V|) and is dropped by capacity slicing.

Offsets dtype contract: the device builds accumulate offsets in int32 (the
natural device width); ``_check_offsets_width`` rejects edge counts that
could wrap instead of silently overflowing.  The host oracle emits int64.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .types import CSR

I32 = jnp.int32

# Device builds accumulate offsets as int32: cumsum(deg) wraps once the edge
# count reaches 2**31.  Checked at trace time (shapes are static) so the
# failure is a clear error, never a silently wrapped CSR.  Module-level so
# tests can exercise the guard without a 2B-edge graph.
INT32_OFFSETS_LIMIT = 2**31 - 1


def _check_offsets_width(num_edges: int) -> None:
    if num_edges > INT32_OFFSETS_LIMIT:
        raise ValueError(
            f"edge count {num_edges} exceeds int32 offsets "
            f"(limit {INT32_OFFSETS_LIMIT}); the device builds accumulate "
            "offsets in int32 — shard the load (load_csr_sharded) or build "
            "on host (csr_np) for graphs this large")


def _ceil_log2(n: int) -> int:
    return max(int(n - 1).bit_length(), 0)


@functools.partial(jax.jit, static_argnames=("num_vertices", "weighted"))
def csr_global(
    src: jax.Array,
    dst: jax.Array,
    weights: Optional[jax.Array],
    num_vertices: int,
    *,
    weighted: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Single-stage build: one global stable sort (baseline)."""
    _check_offsets_width(src.shape[0])
    v = num_vertices
    key = jnp.where(src >= 0, src, v).astype(I32)
    order = jnp.argsort(key, stable=True)
    targets = dst[order]
    w = weights[order] if weighted else None
    deg = jnp.zeros((v,), I32).at[key].add(1, mode="drop")
    offsets = jnp.concatenate([jnp.zeros((1,), I32), jnp.cumsum(deg, dtype=I32)])
    return offsets, targets, w


@functools.partial(jax.jit, static_argnames=("num_vertices", "rho", "weighted"))
def csr_staged(
    src: jax.Array,
    dst: jax.Array,
    weights: Optional[jax.Array],
    num_vertices: int,
    *,
    rho: int = 4,
    weighted: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """GVEL multi-stage build (Algorithm 2, rank-based).

    Stage 1: rho contiguous edge partitions, each locally stable-sorted by
             source, with its degree histogram -> rho partition CSRs
             (vmapped: independent work).
    Stage 2: partition degrees -> global offsets + per-partition bases;
             every partition edge's destination =
             offsets[u] + (edges of u in earlier partitions) + local rank.
             The local rank of the edge at sorted position i is
             i - first_p[u], where first_p[u], the start of u's run in
             the sorted partition, is the exclusive scan of that
             partition's degree histogram; so dest = T[p, u] + i with
             one (rho, V) table T = offsets + before - first.
    The scatter destinations are disjoint, so the merge is race-free, and
    the stable sort keeps each row's targets in file order.
    """
    _check_offsets_width(src.shape[0])
    v = num_vertices
    e = src.shape[0]
    pcap = -(-e // rho)
    pad = rho * pcap - e
    key = jnp.where(src >= 0, src, v).astype(I32)
    if pad:
        key = jnp.concatenate([key, jnp.full((pad,), v, I32)])
        dst = jnp.concatenate([dst, jnp.full((pad,), -1, I32)])
        if weighted:
            weights = jnp.concatenate([weights, jnp.zeros((pad,), weights.dtype)])
    key = key.reshape(rho, pcap)
    dstp = dst.reshape(rho, pcap)
    wp = weights.reshape(rho, pcap) if weighted else None

    # ---- stage 1: partition-local sorts (independent, parallelizable) ----
    if wp is None:
        wp = jnp.zeros_like(key, jnp.float32)   # dummy; DCE'd when unweighted

    def local(keys, dsts, ws):
        order = jnp.argsort(keys, stable=True)
        skey = keys[order]
        deg = jnp.zeros((v,), I32).at[skey].add(1, mode="drop")
        return skey, dsts[order], deg, ws[order]

    skey, sdst, pdeg, sw = jax.vmap(local)(key, dstp, wp)

    # ---- stage 2: global offsets + disjoint merge -------------------------
    deg = jnp.sum(pdeg, axis=0, dtype=I32)                       # (V,)
    offsets = jnp.concatenate([jnp.zeros((1,), I32), jnp.cumsum(deg, dtype=I32)])
    before = jnp.cumsum(pdeg, axis=0, dtype=I32) - pdeg          # (rho, V) excl
    first = jnp.cumsum(pdeg, axis=1, dtype=I32) - pdeg           # run starts
    table = offsets[:-1][None, :] + before - first               # (rho, V)
    dest = (jnp.take_along_axis(table, jnp.clip(skey, 0, v - 1), axis=1)
            + jnp.arange(pcap, dtype=I32)[None, :])
    dest = jnp.where(skey < v, dest, e)                          # drop padding
    targets = jnp.full((e,), -1, I32).at[dest.reshape(-1)].set(
        sdst.reshape(-1), mode="drop")
    w = None
    if weighted:
        w = jnp.zeros((e,), weights.dtype).at[dest.reshape(-1)].set(
            sw.reshape(-1), mode="drop")
    return offsets, targets, w


def _bin_level_widths(v_bits: int, bin_bits: int, avail: int) -> Tuple[int, ...]:
    """Digit widths per level, low bits first.  Each level handles one
    ``bin_bits``-wide slice of the vertex id (clamped to ``avail``, the bits
    an int32 key has left after the position field and the padding
    sentinel); the top level's digit is the bin index itself."""
    width = max(1, min(bin_bits, avail))
    widths = []
    rem = max(v_bits, 1)
    while rem > 0:
        widths.append(min(width, rem))
        rem -= widths[-1]
    return tuple(widths)


@functools.partial(jax.jit, static_argnames=("num_vertices", "bin_bits",
                                             "weighted"))
def csr_binned(
    src: jax.Array,
    dst: jax.Array,
    weights: Optional[jax.Array],
    num_vertices: int,
    *,
    bin_bits: Optional[int] = None,
    weighted: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Propagation-blocking-style binned build (sort-free rank algebra).

    Vertices are cut into contiguous ranges of 2**bin_bits ("bins"); the
    build groups edges one bin digit per level, low bits first, so the
    final level buckets whole bins and every earlier level is the
    contention-free within-bin fill.  Per level the digit and the current
    position are packed into one int32 — (digit << pos_bits) | position —
    and value-sorted: positions make keys unique, so the (unstable,
    fast-path) value sort realizes exactly the stable cumulative-count
    rank, and the low bits of the sorted keys are the level's permutation.
    Levels compose by gather; targets/weights fill by gather through the
    final permutation (disjoint destinations by construction); offsets are
    one degree histogram + cumsum.  No argsort, no payload-carrying
    comparator sort, no scatters.

    Padding (src == -1) carries a sentinel digit in the top level only —
    lexicographically that is enough to sink it below every real edge.

    bin_bits defaults to the widest digit an int32 key can carry, which
    minimizes the level count (usually 1-2 levels).
    """
    _check_offsets_width(src.shape[0])
    v = num_vertices
    e = src.shape[0]
    v_bits = _ceil_log2(v)
    pos_bits = max(_ceil_log2(e), 1)
    avail = 31 - pos_bits - 1          # -1: top-level padding sentinel bit
    if avail < 1:
        raise ValueError(
            f"csr_binned needs ceil(log2(E)) <= 29 to pack int32 level keys "
            f"(E={e}); use csr_staged or shard the load")
    widths = _bin_level_widths(v_bits, avail if bin_bits is None else bin_bits,
                               avail)
    valid = src >= 0
    iota = jnp.arange(e, dtype=I32)
    pos_mask = (1 << pos_bits) - 1
    perm = iota
    shift = 0
    for li, width in enumerate(widths):
        cur = src if li == 0 else src[perm]
        dig = (cur >> shift) & ((1 << width) - 1)
        if li == len(widths) - 1:
            pad = valid if li == 0 else valid[perm]
            dig = jnp.where(pad, dig, 1 << width)
        key = (dig.astype(I32) << pos_bits) | iota
        level = jax.lax.sort(key) & pos_mask
        perm = level if li == 0 else perm[level]
        shift += width
    targets = dst[perm]
    w = weights[perm] if weighted else None
    deg = jnp.zeros((v,), I32).at[jnp.clip(src, 0, v - 1)].add(
        valid.astype(I32))
    offsets = jnp.concatenate([jnp.zeros((1,), I32), jnp.cumsum(deg, dtype=I32)])
    return offsets, targets, w


def csr_binned_np(src: np.ndarray, dst: np.ndarray,
                  weights: Optional[np.ndarray], num_vertices: int, *,
                  bin_bits: Optional[int] = None,
                  num_workers: int = 1) -> CSR:
    """Host binned build: bucket edges by contiguous vertex range, then
    fill each bin independently (cache-sized subproblems; threads across
    bins — numpy's sort releases the GIL).

    Bucketing is the cumulative-count rank, one pass per bin (B small):
    dest = bin_start[bin] + arrival rank within bin.  The per-bin fill
    value-sorts (local_id << 32) | within_bin_position packed into int64 —
    unique keys, so the plain value sort is the stable rank, and targets /
    weights land by gather through disjoint per-bin destinations."""
    from concurrent.futures import ThreadPoolExecutor

    v = num_vertices
    m = src >= 0
    src = np.ascontiguousarray(src[m], np.int64)
    dst = dst[m]
    weights = weights[m] if weights is not None else None
    e = len(src)
    v_bits = _ceil_log2(v)
    if bin_bits is None:
        bin_bits = max(v_bits - 4, 1)        # ~16 bins by default
    bin_bits = max(bin_bits, 1)
    nbins = max((v + (1 << bin_bits) - 1) >> bin_bits, 1)

    deg = np.bincount(src, minlength=v)
    offsets = np.zeros(v + 1, np.int64)
    np.cumsum(deg, out=offsets[1:])
    targets = np.empty(e, np.int32)
    wout = np.empty(e, weights.dtype) if weights is not None else None
    if e == 0:
        return CSR(offsets, targets, wout, v)

    # ---- bucket: cumulative-count rank into bins (one cumsum per bin) ----
    bins = src >> bin_bits
    bcount = np.bincount(bins, minlength=nbins)
    bstart = np.zeros(nbins + 1, np.int64)
    np.cumsum(bcount, out=bstart[1:])
    dest1 = np.empty(e, np.int64)
    for b in range(nbins):
        hit = bins == b
        dest1[hit] = bstart[b] + np.arange(int(bcount[b]))
    perm1 = np.empty(e, np.int64)
    perm1[dest1] = np.arange(e)

    # ---- per-bin contention-free fills (threadable, cache-sized) --------
    def fill(b):
        lo, hi = int(bstart[b]), int(bstart[b + 1])
        if lo == hi:
            return
        edges = perm1[lo:hi]
        local = src[edges] & ((1 << bin_bits) - 1)
        packed = (local << 32) | np.arange(hi - lo)
        order = np.sort(packed) & 0xFFFFFFFF
        csr_order = edges[order]
        targets[lo:hi] = dst[csr_order]
        if wout is not None:
            wout[lo:hi] = weights[csr_order]

    if num_workers == 1 or nbins == 1:
        for b in range(nbins):
            fill(b)
    else:
        with ThreadPoolExecutor(num_workers) as pool:
            list(pool.map(fill, range(nbins)))
    return CSR(offsets, targets, wout, v)


def csr_staged_np(src: np.ndarray, dst: np.ndarray,
                  weights: Optional[np.ndarray], num_vertices: int, *,
                  rho: int = 4, num_workers: int = 1) -> CSR:
    """Host (numpy) staged build with a thread pool over partitions —
    the multicore realization of Algorithm 2: partition-local sorts run
    on separate cores (numpy sort releases the GIL), then the disjoint
    merge scatters in parallel."""
    from concurrent.futures import ThreadPoolExecutor

    v = num_vertices
    e = len(src)
    cuts = np.linspace(0, e, rho + 1).astype(np.int64)

    def local(p):
        s = src[cuts[p]:cuts[p + 1]]
        d = dst[cuts[p]:cuts[p + 1]]
        order = np.argsort(s, kind="stable")
        skey = s[order]
        deg = np.bincount(skey, minlength=v)
        w = weights[cuts[p]:cuts[p + 1]][order] if weights is not None else None
        return skey, d[order], deg, w

    if num_workers == 1:
        parts = [local(p) for p in range(rho)]
    else:
        with ThreadPoolExecutor(num_workers) as pool:
            parts = list(pool.map(local, range(rho)))

    pdeg = np.stack([p[2] for p in parts])                 # (rho, V)
    deg = pdeg.sum(axis=0)
    offsets = np.zeros(v + 1, np.int64)
    np.cumsum(deg, out=offsets[1:])
    before = np.cumsum(pdeg, axis=0) - pdeg                # (rho, V) excl
    targets = np.empty(e, np.int32)
    wout = np.empty(e, np.float32) if weights is not None else None

    def merge(p):
        skey, sdst, pdg, w = parts[p]
        local_off = np.zeros(v + 1, np.int64)
        np.cumsum(pdg, out=local_off[1:])
        rank = np.arange(len(skey)) - local_off[skey]
        dest = offsets[skey] + before[p][skey] + rank
        targets[dest] = sdst
        if wout is not None:
            wout[dest] = w

    if num_workers == 1:
        for p in range(rho):
            merge(p)
    else:
        with ThreadPoolExecutor(num_workers) as pool:
            list(pool.map(merge, range(rho)))
    return CSR(offsets, targets, wout, v)


def csr_np(src: np.ndarray, dst: np.ndarray, weights: Optional[np.ndarray],
           num_vertices: int) -> CSR:
    """Host oracle: numpy stable sort."""
    m = src >= 0
    src, dst = src[m], dst[m]
    weights = weights[m] if weights is not None else None
    order = np.argsort(src, kind="stable")
    deg = np.bincount(src, minlength=num_vertices)
    offsets = np.zeros(num_vertices + 1, np.int64)
    np.cumsum(deg, out=offsets[1:])
    return CSR(offsets, dst[order].astype(np.int32),
               None if weights is None else weights[order],
               num_vertices)
