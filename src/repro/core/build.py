"""Edgelist -> CSR construction (GVEL §4.2.3-4.2.4, TPU-adapted).

There is no fetch-add on TPU, so PIGO's "claim a slot atomically" becomes a
deterministic *rank*: edge e with source u lands at offsets[u] + (rank of e
among u's edges).  Ranks come from a stable sort and the exclusive scan of
the degree histogram, which makes construction a pure gather/scatter with
provably disjoint destinations.

* ``csr_staged``    — GVEL's multi-stage build, the one device build:
                      edges are cut into rho contiguous partitions; each
                      partition sorts locally (smaller sorts, independent
                      -> parallel across cores or devices) and is merged
                      into the global CSR through per-partition base
                      offsets.  Stage-local work is contention-free; only
                      the merge touches shared state, and its destinations
                      are disjoint by construction.
* ``csr_np``        — the host oracle (one numpy stable sort).

Fixed-capacity buffers use src == -1 as padding; padding sorts to the end
(key |V|) and is dropped by capacity slicing.

Offsets dtype contract: the device build accumulates offsets in int32 (the
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

# The device build accumulates offsets as int32: cumsum(deg) wraps once the
# edge count reaches 2**31.  Checked at trace time (shapes are static) so the
# failure is a clear error, never a silently wrapped CSR.  Module-level so
# tests can exercise the guard without a 2B-edge graph.
INT32_OFFSETS_LIMIT = 2**31 - 1


def _check_offsets_width(num_edges: int) -> None:
    if num_edges > INT32_OFFSETS_LIMIT:
        raise ValueError(
            f"edge count {num_edges} exceeds int32 offsets "
            f"(limit {INT32_OFFSETS_LIMIT}); the device build accumulates "
            "offsets in int32 — shard the load (load_csr_sharded) or build "
            "on host (csr_np) for graphs this large")


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
