"""Pallas TPU kernel: batched CSR row gather (fixed-width neighbor slices).

The consumer side of GVEL's CSR: the random-walk sampler (repro.data.walks)
needs, for a batch of vertices, a fixed-width window of each vertex's
adjacency row plus its degree.  On TPU this is one DMA-friendly dynamic
slice per vertex: offsets live in SMEM-like scalar storage, the targets
array streams through VMEM via `pl.ds` dynamic slices — the pattern paged
attention uses for KV lookup, applied to graph adjacency.

Each grid step handles one batch tile of vertices with a fori_loop of
dynamic loads; out-of-row lanes are masked to -1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

I32 = jnp.int32


def _gather_body(u_ref, off_ref, tgt_ref, out_ref, deg_ref, *, width: int):
    bt = u_ref.shape[-1]

    def one(i, _):
        u = u_ref[0, i]
        lo = off_ref[u]
        hi = off_ref[u + 1]
        deg = hi - lo
        # clamp the slice start so the fixed-width window stays in bounds
        start = jnp.minimum(lo, jnp.maximum(tgt_ref.shape[-1] - width, 0))
        row = tgt_ref[pl.ds(start, width)]
        lane = jax.lax.iota(I32, width)
        shifted = lo - start
        valid = (lane >= shifted) & (lane < shifted + jnp.minimum(deg, width))
        # re-align so lane 0 is the first neighbor
        row = jnp.roll(row, -shifted)
        valid = jnp.roll(valid, -shifted)
        out_ref[i, :] = jnp.where(valid, row, -1)
        deg_ref[0, i] = deg
        return 0

    jax.lax.fori_loop(0, bt, one, 0)


@functools.partial(jax.jit, static_argnames=("width", "bt", "interpret"))
def neighbor_gather_kernel(vertices: jax.Array, offsets: jax.Array,
                           targets: jax.Array, *, width: int = 128,
                           bt: int = 256, interpret: bool = True):
    """vertices (B,), offsets (V+1,), targets (E,) ->
    (neighbors (B, width) padded -1, degrees (B,))."""
    b = vertices.shape[0]
    pb = -(-b // bt) * bt
    if pb != b:
        vertices = jnp.concatenate([vertices, jnp.zeros((pb - b,), I32)])
    v2 = vertices.reshape(pb // bt, bt)
    out, deg = pl.pallas_call(
        functools.partial(_gather_body, width=width),
        grid=(pb // bt,),
        in_specs=[
            pl.BlockSpec((1, bt), lambda i: (i, 0)),
            pl.BlockSpec(offsets.shape, lambda i: (0,)),   # whole offsets
            pl.BlockSpec(targets.shape, lambda i: (0,)),   # whole targets
        ],
        out_specs=(
            pl.BlockSpec((bt, width), lambda i: (i, 0)),
            pl.BlockSpec((1, bt), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((pb, width), I32),
            jax.ShapeDtypeStruct((pb // bt, bt), I32),
        ),
        interpret=interpret,
    )(v2, offsets, targets)
    return out[:b], deg.reshape(-1)[:b]
