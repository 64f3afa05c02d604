"""Vectorized edgelist parsing (the TPU adaptation of GVEL Algorithm 1).

GVEL's CPU hot loop walks bytes with a pointer and custom digit parsers.
On a vector machine the same work is mask/scan algebra over a whole block:

  1. classify every byte at once (digit / dot / minus / newline / space),
  2. form *token* segments (maximal runs of number chars) and *line*
     segments (split at newlines) from cumulative sums,
  3. combine digits into values with segment reductions
     (value = sum digit_i * 10^(#digits after i in the token)),
  4. assemble (src, dst, weight) per line and compact valid, *owned*
     lines into a fixed-capacity edge buffer (GVEL's over-allocation:
     capacity is a bytes-derived upper bound, untouched tail stays padding).

Block-boundary handling replaces GVEL's getBlock() pointer repositioning
with uniform tiles + a left overlap + an ownership mask: every block buffer
carries `overlap` bytes of left context, and a line belongs to the block
whose *owned byte range* contains the line's terminating newline.  This is
branch-free and identical for every block, so one jitted program serves all.

One per-byte core, :func:`_parse_block_bytes`, carries that algebra in
*sorted-segment* form: token/line ids increase with byte position, so
every per-token and per-line quantity is a cumulative max/sum plus a
gather instead of a scatter — on CPU XLA a scatter runs ~5M elem/s
while cumsum/gather run 20-100M elem/s.  Two entry points wrap it:

* :func:`parse_block` / :func:`parse_blocks` — block in, fixed-capacity
  per-block ``(src, dst, w, count)`` out (one compaction scatter per
  block).  The standalone parser: unit tests, the Pallas kernel's XLA
  reference, and the historical batch pipeline all consume it.
* :func:`parse_accumulate` — the streaming loader's fused hot path: a
  whole batch of blocks in, edges packed **directly into the packed
  device accumulators** (donated, so the update is in-place where the
  backend supports buffer donation — see :func:`donation_supported`).
  The per-block ``(nb, edge_cap)`` intermediates of the two-step
  parse-then-accumulate pipeline never materialize; the batch-wide
  compaction (:func:`_compact_accumulate`) costs exactly one scatter
  per batch, which is where the streaming engine's speedup over the
  batch round-trip lives.  The Pallas engine shares the same
  compaction through ``kernels.parse_edges.parse_edges_accumulate``.

Limits (documented): vertex ids must have <= 9 decimal digits (int32 math;
covers every graph in the paper, max |V| = 214M), weights are plain
decimals (no exponent notation), and no line may exceed `overlap` bytes
(violations that cross a block boundary are detected during staging and
raise — see ``blocks.stage_blocks``; ``docs/performance.md`` has the
remedy).  ``parse_accumulate`` computes weight mantissas exactly in
integer arithmetic and rounds to float32 once, so weights match
``parse_block`` bit-for-bit up to 7 significant digits; 8+ digit
mantissas may differ in the last ulp (both paths round, in different
orders).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .trace import span

I32 = jnp.int32

# byte classes
_NL, _CR, _SP, _TAB, _DOT, _MINUS = 10, 13, 32, 9, 46, 45


def _scatter_set(cap: int, select, index, values, fill, dtype):
    """out[index[i]] = values[i] where select[i]; OOB indices dropped."""
    out = jnp.full((cap,), fill, dtype)
    idx = jnp.where(select, index, cap)
    return out.at[idx].set(values.astype(dtype), mode="drop")


@functools.partial(
    jax.jit,
    static_argnames=("weighted", "base", "edge_cap", "max_digits"),
)
def parse_block(
    buf: jax.Array,
    owned_start: jax.Array,
    owned_end: jax.Array,
    *,
    weighted: bool,
    base: int,
    edge_cap: int,
    max_digits: int = 9,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array], jax.Array]:
    """Parse one byte block into fixed-capacity (src, dst, w, count).

    buf:  (n,) uint8, newline-padded.  A line is *owned* iff the index of
    its terminating newline lies in [owned_start, owned_end).
    Returns int32 src/dst (padded with -1), float32 w or None, int32 count.

    A thin wrapper over the per-byte sorted-segment core
    (:func:`_parse_block_bytes`) plus one compaction scatter — lines
    compact in terminating-newline order, which is line order.
    """
    n = buf.shape[0]
    valid, src_b, dst_b, w_b = _parse_block_bytes(
        buf, owned_start, owned_end, weighted=weighted, base=base,
        max_digits=max_digits)
    pos = jnp.cumsum(valid.astype(I32)) - 1
    count = jnp.maximum(pos[-1] + 1, 0)
    # the block's only scatter: pack the valid newline byte positions;
    # values then come from gathers at those positions
    packed = _scatter_set(edge_cap, valid, pos,
                          jnp.arange(n, dtype=I32), n, I32)
    pv = packed < n
    pc = jnp.minimum(packed, n - 1)
    src = jnp.where(pv, src_b[pc], -1)
    dst = jnp.where(pv, dst_b[pc], -1)
    w = jnp.where(pv, w_b[pc], 0.0) if weighted else None
    return src, dst, w, count


@functools.partial(
    jax.jit, static_argnames=("weighted", "base", "edge_cap", "max_digits")
)
def parse_blocks(
    bufs: jax.Array,
    owned_start: jax.Array,
    owned_end: jax.Array,
    *,
    weighted: bool,
    base: int,
    edge_cap: int,
    max_digits: int = 9,
):
    """vmap of parse_block over a batch of equally-sized blocks."""
    fn = functools.partial(parse_block, weighted=weighted, base=base,
                           edge_cap=edge_cap, max_digits=max_digits)
    return jax.vmap(fn)(bufs, owned_start, owned_end)


# ---------------------------------------------------------------------------
# fused parse -> accumulate (the streaming loader's hot path)
# ---------------------------------------------------------------------------

def _parse_block_bytes(buf, owned_start, owned_end, *, weighted: bool,
                       base: int, max_digits: int = 9):
    """Per-byte fused parse of one block: ``(valid, src, dst, w)`` in the
    byte domain.

    ``valid[i]`` is True iff byte ``i`` is an *owned* newline terminating
    a well-formed edge line; ``src``/``dst``/``w`` carry that line's
    parsed values at those bytes (garbage elsewhere — consumers gather
    at valid positions only).  Token/line ids increase with byte
    position, so every per-token and per-line quantity is a cumulative
    max/sum plus a gather — no scatters at all.  Integer token values
    come from a wrapped int32 cumulative sum — per-token differences
    are exact for <= ``max_digits`` digit tokens.  The Pallas kernel
    (``kernels.parse_edges``) realizes this same algebra in VMEM; both
    wrappers (:func:`parse_block`, :func:`parse_accumulate`) and the
    kernel therefore agree bit-for-bit.
    """
    n = buf.shape[0]
    d = buf.astype(I32)
    idx = jnp.arange(n, dtype=I32)

    is_digit = (d >= 48) & (d <= 57)
    is_dot = d == _DOT
    is_minus = d == _MINUS
    is_tok = is_digit | is_dot | is_minus
    is_nl = d == _NL
    is_ws = (d == _SP) | (d == _TAB) | (d == _CR)
    is_bad = ~(is_tok | is_nl | is_ws)

    prev_tok = jnp.concatenate([jnp.zeros((1,), bool), is_tok[:-1]])
    tok_start = is_tok & ~prev_tok
    next_tok = jnp.concatenate([is_tok[1:], jnp.zeros((1,), bool)])
    tok_end = is_tok & ~next_tok

    cum_ts = jnp.cumsum(tok_start.astype(I32))     # token starts <= i
    cum_dig = jnp.cumsum(is_digit.astype(I32))     # digits <= i

    # my token's end/start byte position, per byte (valid at token bytes:
    # tokens never span newlines, so runs are well-nested)
    end_pos = jax.lax.cummin(jnp.where(tok_end, idx, n - 1), reverse=True)
    start_pos = jax.lax.cummax(jnp.where(tok_start, idx, 0))

    # digits strictly after byte i within its token
    digits_after = jnp.clip(cum_dig[end_pos] - cum_dig, 0, max_digits)
    pow10_i = 10 ** jnp.arange(max_digits + 1, dtype=I32)
    contrib = jnp.where(is_digit, (d - 48) * pow10_i[digits_after], 0)
    csum_c = jnp.cumsum(contrib)       # int32 wraps; per-token diff is exact
    excl_c = csum_c - contrib
    # integer value of the token ending at byte i (valid at token ends)
    tok_val = csum_c - excl_c[start_pos]

    # latest newline strictly before byte i (-1: none)
    pex = jnp.concatenate([
        jnp.full((1,), -1, I32),
        jax.lax.cummax(jnp.where(is_nl, idx, -1))[:-1]])
    # token starts up to my line's opening newline
    cts_at = jnp.where(pex < 0, 0, cum_ts[jnp.maximum(pex, 0)])
    # my token's 0-based ordinal within its line (valid at token ends)
    ord_in_line = cum_ts - 1 - cts_at

    def role_pos(k):
        """Latest byte <= i ending a token with line-ordinal k."""
        return jax.lax.cummax(jnp.where(tok_end & (ord_in_line == k), idx, -1))

    p0, p1 = role_pos(0), role_pos(1)
    bad_pos = jax.lax.cummax(jnp.where(is_bad, idx, -1))

    owned = (idx >= owned_start) & (idx < owned_end)
    # ">= 2 tokens in the line" <=> a role-1 token ends inside it
    valid = is_nl & owned & (p1 > pex) & ~(bad_pos > pex)

    src = tok_val[jnp.maximum(p0, 0)] - base
    dst = tok_val[jnp.maximum(p1, 0)] - base

    w = None
    if weighted:
        p2 = role_pos(2)
        dot_pos = jax.lax.cummax(jnp.where(is_dot, idx, -1))
        minus_pos = jax.lax.cummax(jnp.where(is_minus, idx, -1))
        p2c = jnp.maximum(p2, 0)
        w_start = start_pos[p2c]
        dot_of = dot_pos[p2c]
        frac_len = jnp.where(dot_of >= w_start,
                             cum_dig[p2c] - cum_dig[jnp.maximum(dot_of, 0)], 0)
        pow10_f = jnp.float32(10.0) ** jnp.arange(max_digits + 1)
        wf = tok_val[p2c].astype(jnp.float32) \
            / pow10_f[jnp.clip(frac_len, 0, max_digits)]
        wf = jnp.where(minus_pos[p2c] >= w_start, -wf, wf)
        w = jnp.where(p2 > pex, wf, 1.0)       # missing weight -> 1
    return valid, src, dst, w


def _compact_accumulate(acc_src, acc_dst, acc_w, total, valid, src, dst, w,
                        *, edge_bound: int):
    """Pack a batch of per-byte parses into the accumulators at ``total``.

    ``valid``/``src``/``dst``/``w`` are ``(nb, blen)`` byte-domain
    outputs of :func:`_parse_block_bytes` (or the Pallas kernel's
    byte-domain realization of it — ``kernels.parse_edges`` fuses the
    same compaction after its kernel).  Blocks pack consecutively and
    edges within a block stay in line order — the same edge order the
    two-step parse_blocks + accumulate pipeline produced.
    """
    valid_f = valid.reshape(-1)
    flat_n = valid_f.shape[0]
    # batch-wide exclusive compaction
    dest = jnp.cumsum(valid_f.astype(I32)) - 1
    count = jnp.maximum(dest[-1] + 1, 0)
    # one scatter packs byte positions; values then come from gathers
    # (scatter is the slow primitive on CPU XLA — use exactly one)
    pos = jnp.full((edge_bound,), flat_n, I32).at[
        jnp.where(valid_f, dest, edge_bound)].set(
            jnp.arange(flat_n, dtype=I32), mode="drop")
    pv = pos < flat_n
    posc = jnp.minimum(pos, flat_n - 1)
    src_w = jnp.where(pv, src.reshape(-1)[posc], -1)
    dst_w = jnp.where(pv, dst.reshape(-1)[posc], -1)
    # a fixed-size window written at the running offset: with donation
    # this lowers to an in-place memcpy of edge_bound elements; invalid
    # window slots carry the accumulator's padding values, and the next
    # batch's window starts where this batch's edges end, so padding
    # never buries an edge
    acc_src = jax.lax.dynamic_update_slice(acc_src, src_w, (total,))
    acc_dst = jax.lax.dynamic_update_slice(acc_dst, dst_w, (total,))
    if acc_w is not None and w is not None:
        w_w = jnp.where(pv, w.reshape(-1)[posc], 0.0)
        acc_w = jax.lax.dynamic_update_slice(acc_w, w_w, (total,))
    return acc_src, acc_dst, acc_w, total + count


def _parse_accumulate_impl(acc_src, acc_dst, acc_w, total, bufs,
                           owned_start, owned_end, *, weighted: bool,
                           base: int, edge_bound: int, max_digits: int = 9):
    fn = functools.partial(_parse_block_bytes, weighted=weighted, base=base,
                           max_digits=max_digits)
    valid, src, dst, w = jax.vmap(fn)(bufs, owned_start, owned_end)
    return _compact_accumulate(acc_src, acc_dst, acc_w, total, valid, src,
                               dst, w, edge_bound=edge_bound)


@functools.lru_cache(maxsize=None)
def _parse_accumulate_jit(donate: bool):
    return jax.jit(
        _parse_accumulate_impl,
        static_argnames=("weighted", "base", "edge_bound", "max_digits"),
        donate_argnums=(0, 1, 2) if donate else ())


@functools.lru_cache(maxsize=None)
def donation_supported() -> bool:
    """Probe whether this backend honors ``donate_argnums`` (in-place
    buffer reuse).  CPU and TPU do on current jaxlib; a backend that
    refuses donation leaves the input buffer alive — callers fall back
    to the same program without donation (one extra buffer copy per
    batch, same results).  Cached per process."""
    probe = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    x = jnp.zeros((8,), I32)
    probe(x).block_until_ready()
    return x.is_deleted()


def parse_accumulate(acc_src, acc_dst, acc_w, total, bufs, owned_start,
                     owned_end, *, weighted: bool, base: int,
                     edge_bound: int, max_digits: int = 9,
                     donate: Optional[bool] = None):
    """Fused batch parse + packed accumulation (one jitted program).

    Parses ``bufs`` (nb, buf_len) and writes the batch's edges into the
    packed accumulators at offset ``total``, returning the updated
    ``(acc_src, acc_dst, acc_w, total)``.  ``edge_bound`` is the static
    per-batch edge capacity (``nb * plan.edge_cap``); the caller must
    guarantee ``total + edge_bound <= len(acc_src)`` (the loader sizes
    the accumulators so trimmed batches always fit exactly).

    ``donate=None`` probes the backend once and donates the accumulator
    buffers when supported — the update then happens in place, instead
    of copying the full capacity-sized buffers every batch.  **Donated
    inputs are consumed**: callers must rebind (never reuse) the passed
    accumulators, exactly like the loader's streaming loop does.
    ``donate=False`` is the documented fallback for backends that
    refuse donation (and for callers that want to keep their inputs).
    """
    if donate is None:
        donate = donation_supported()
    return _parse_accumulate_jit(bool(donate))(
        acc_src, acc_dst, acc_w, total, bufs, owned_start, owned_end,
        weighted=weighted, base=base, edge_bound=edge_bound,
        max_digits=max_digits)


def make_accumulators(cap: int, *, weighted: bool, device=None):
    """Fresh packed edge accumulators: ``(src=-1, dst=-1, w=0, total=0)``.

    The one place the accumulator layout (padding values, dtypes) is
    written down — the streaming loader, the tuner's measurement pass,
    and the sharded loader all start from here.  ``device`` commits the
    buffers to a specific device: jit follows committed inputs, so the
    whole donated parse+accumulate chain then runs on that device (the
    sharded loader places shard k's accumulators on mesh device k and
    the per-shard parses execute concurrently with no cross-device
    traffic).
    """
    cap = max(int(cap), 1)
    put = (jnp.asarray if device is None
           else functools.partial(jax.device_put, device=device))
    with span("load.accumulators"):
        acc_src = put(np.full((cap,), -1, np.int32))
        acc_dst = put(np.full((cap,), -1, np.int32))
        acc_w = put(np.zeros((cap,), np.float32)) if weighted else None
        return acc_src, acc_dst, acc_w, put(np.zeros((), np.int32))
