"""Jit'd public wrappers for the parse_edges Pallas kernel.

Two entries share the byte-domain kernel (``parse_bytes_kernel``):

* :func:`parse_edges` — packed per-block ``(src, dst, w, counts)``; the
  historical contract used by the allclose test sweeps.
* :func:`parse_edges_accumulate` — the Pallas engine's streaming hot
  path: kernel parse and the batch-wide compaction into the donated
  packed accumulators run as **one jitted program**, exactly mirroring
  ``core.parse.parse_accumulate`` (the compaction is literally shared —
  ``core.parse._compact_accumulate``).  The per-block ``(nb, edge_cap)``
  intermediates and the separate scatter-accumulate program of the old
  two-step pipeline never materialize.

The kernel runs only in Pallas interpret mode.  It does not lower for
TPU yet: Mosaic refuses its ``(1, buf_len)`` block shape when a batch
has more than one block, and has no ``cumsum`` lowering for its body.
So :func:`parse_edges_accumulate` defaults to ``use_kernel=False``, the
pure-jnp twin (the same byte-domain values, compiled by XLA), on every
backend; the kernel runs only when a caller asks for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import parse as parse_core
from ...core.blocks import MIN_LINE_BYTES
from .kernel import parse_bytes_kernel, parse_edges_kernel
from .ref import parse_edges_ref


def parse_edges(bufs, owned_start: int, owned_end: int, *, weighted: bool = False,
                base: int = 1, edge_cap: int | None = None,
                use_kernel: bool = True, interpret: bool = True):
    """Parse (nb, buf_len) text blocks -> (src, dst, w, counts).

    use_kernel=False runs the pure-jnp oracle (the XLA path) instead of
    the interpret-mode kernel.
    """
    nb, buf_len = bufs.shape
    if edge_cap is None:
        edge_cap = buf_len // MIN_LINE_BYTES + 2
    owned = jnp.asarray([owned_start, owned_end], jnp.int32)
    if use_kernel:
        return parse_edges_kernel(bufs, owned, weighted=weighted, base=base,
                                  edge_cap=edge_cap, interpret=interpret)
    return parse_edges_ref(bufs, owned, weighted=weighted, base=base,
                           edge_cap=edge_cap)


def _fused_impl(acc_src, acc_dst, acc_w, total, bufs, owned, *,
                weighted: bool, base: int, edge_bound: int, max_digits: int,
                use_kernel: bool):
    if use_kernel:
        valid, src, dst, w = parse_bytes_kernel(
            bufs, owned, weighted=weighted, base=base, max_digits=max_digits,
            interpret=True)
    else:
        fn = functools.partial(parse_core._parse_block_bytes,
                               weighted=weighted, base=base,
                               max_digits=max_digits)
        valid, src, dst, w = jax.vmap(
            lambda b: fn(b, owned[0], owned[1]))(bufs)
    return parse_core._compact_accumulate(
        acc_src, acc_dst, acc_w, total, valid, src, dst, w,
        edge_bound=edge_bound)


@functools.lru_cache(maxsize=None)
def _fused_jit(donate: bool):
    return jax.jit(
        _fused_impl,
        static_argnames=("weighted", "base", "edge_bound", "max_digits",
                         "use_kernel"),
        donate_argnums=(0, 1, 2) if donate else ())


def parse_edges_accumulate(acc_src, acc_dst, acc_w, total, bufs,
                           owned_start: int, owned_end: int, *,
                           weighted: bool = False, base: int = 1,
                           edge_bound: int | None = None,
                           max_digits: int = 9,
                           use_kernel: bool = False,
                           donate: bool | None = None):
    """Fused kernel parse + donated packed accumulation (one program).

    Drop-in peer of ``core.parse.parse_accumulate``: parses ``bufs``
    (nb, buf_len) and writes the batch's edges into the packed
    accumulators at offset ``total``, returning the updated
    ``(acc_src, acc_dst, acc_w, total)``.  Donated inputs are consumed —
    rebind, never reuse, the passed accumulators.  ``use_kernel=True``
    parses in the Pallas kernel, in interpret mode (see the module
    docstring).
    """
    nb, buf_len = bufs.shape
    if edge_bound is None:
        edge_bound = nb * (buf_len // MIN_LINE_BYTES + 2)
    if donate is None:
        donate = parse_core.donation_supported()
    owned = jnp.asarray([owned_start, owned_end], jnp.int32)
    return _fused_jit(bool(donate))(
        acc_src, acc_dst, acc_w, total, bufs, owned, weighted=weighted,
        base=base, edge_bound=edge_bound, max_digits=max_digits,
        use_kernel=bool(use_kernel))
