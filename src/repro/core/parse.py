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
every per-token and per-line quantity is a *fill* — a value carried
from a marked byte to the bytes after (or before) it — made of
cumulative max/min/sum scans and elementwise work, with no scatter and
no gather.  Scatters were dropped first (on CPU XLA a scatter runs ~5M
elem/s, a cumsum or gather 20-100M); then the gathers that had replaced
them: on a TPU v5e a byte-domain gather costs about 8 ns a byte
whatever its table's size (~17 ms a 2 MB batch), while the compiler
runs each scan as a tiled reduce-window tree in under 1 ms a batch.
Two entry points wrap it:

* :func:`parse_block` / :func:`parse_blocks` — block in, fixed-capacity
  per-block ``(src, dst, w, count)`` out (one compaction scatter per
  block).  The standalone parser: unit tests, the Pallas kernel's XLA
  reference, and the historical batch pipeline all consume it.
* :func:`parse_accumulate` — the streaming loader's fused hot path: a
  whole batch of blocks in, edges packed **directly into the packed
  device accumulators** (donated, so the update is in-place where the
  backend supports buffer donation — see :func:`donation_supported`).
  The per-block ``(nb, edge_cap)`` intermediates of the two-step
  parse-then-accumulate pipeline never materialize.  The batch-wide
  compaction (:func:`_compact_accumulate`) folds each block 4:1 first,
  since an edge line is at least ``blocks.MIN_LINE_BYTES`` long, and
  scatters the values over a quarter of the batch's bytes, with no
  gather.  The Pallas engine shares the same compaction through
  ``kernels.parse_edges.parse_edges_accumulate``.

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

from .blocks import MIN_LINE_BYTES
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

def _pow10(k, max_digits: int, dtype):
    """``10 ** k`` for ``0 <= k <= max_digits``, elementwise: a ladder over
    k's bits whose factors and partial products are exact powers of ten,
    so float32 results are exact too (10^10 is)."""
    p = jnp.ones(k.shape, dtype)
    for b in range(max_digits.bit_length()):
        p = jnp.where(((k >> b) & 1) == 1,
                      p * jnp.asarray(10 ** (1 << b), dtype), p)
    return p


def _fill_forward(mark, value, ordinal, ord_max: int):
    """Per byte ``i``: ``value`` at the latest marked byte ``<= i``, and
    that byte's ``ordinal`` (-1, with a garbage value, before any mark).

    ``ordinal`` must strictly increase from one marked byte to the next
    and lie in ``[0, ord_max]``.  Each piece of the value's 32 bits rides
    below the ordinal in one int32 ``cummax``, so the latest mark wins
    every piece: a fill made of scans, with no gather.
    """
    piece = 31 - ord_max.bit_length()
    if piece < 8:
        raise ValueError(f"ordinals up to {ord_max} leave no room to fill: "
                         "the block is too long")
    mask = (1 << piece) - 1
    bits = jax.lax.bitcast_convert_type(value, I32)
    out = jnp.zeros_like(bits)
    for shift in range(0, 32, piece):
        part = jax.lax.shift_right_logical(bits, shift) & mask
        key = jax.lax.cummax(jnp.where(mark, (ordinal << piece) | part, -1))
        out = out | ((key & mask) << shift)
    return jax.lax.bitcast_convert_type(out, value.dtype), key >> piece


def _parse_block_bytes(buf, owned_start, owned_end, *, weighted: bool,
                       base: int, max_digits: int = 9):
    """Per-byte fused parse of one block: ``(valid, src, dst, w)`` in the
    byte domain.

    ``valid[i]`` is True iff byte ``i`` is an *owned* newline terminating
    a well-formed edge line; ``src``/``dst``/``w`` carry that line's
    parsed values at those bytes (garbage elsewhere — consumers gather
    at valid positions only).  Token/line ids increase with byte
    position, so every per-token and per-line quantity is a *fill*: a
    value carried from a marked byte (a token's start or end, a newline)
    to the bytes after it, or before it.  Each fill is cumulative scans
    plus elementwise work (:func:`_fill_forward`) — no scatter and no
    gather.  Integer token values come from a wrapped int32 cumulative
    sum — per-token differences are exact for <= ``max_digits`` digit
    tokens.  At valid bytes the outputs equal, bit for bit, those of the
    gather form of this algebra that the Pallas kernel
    (``kernels.parse_edges``) realizes.
    """
    if max_digits > 9:
        raise ValueError(f"max_digits={max_digits}: token values are int32")
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

    # token starts <= i: my token's ordinal, distinct at token ends and
    # at token starts, and at most (n + 1) // 2 (starts are 2 bytes apart)
    cum_ts = jnp.cumsum(tok_start.astype(I32))
    cum_dig = jnp.cumsum(is_digit.astype(I32))     # digits <= i
    fill = functools.partial(_fill_forward, ordinal=cum_ts,
                             ord_max=(n + 1) // 2)

    # digits strictly after byte i within its token: cum_dig never
    # decreases, so its value at my token's end is a reverse cummin
    dig_at_end = jax.lax.cummin(
        jnp.where(tok_end, cum_dig, jnp.iinfo(np.int32).max), reverse=True)
    digits_after = jnp.clip(dig_at_end - cum_dig, 0, max_digits)
    contrib = jnp.where(is_digit,
                        (d - 48) * _pow10(digits_after, max_digits, I32), 0)
    csum_c = jnp.cumsum(contrib)       # int32 wraps; per-token diff is exact
    # integer value of the token ending at byte i (valid at token ends):
    # csum_c less its value just before my token's start
    tok_val = csum_c - fill(tok_start, csum_c - contrib)[0]

    # token starts up to my line's opening newline (cum_ts never
    # decreases, so a cummax carries it; shifted: strictly before i)
    cts_nl = jax.lax.cummax(jnp.where(is_nl, cum_ts, 0))
    cts_at = jnp.concatenate([jnp.zeros((1,), I32), cts_nl[:-1]])
    # my token's 0-based ordinal within its line (valid at token bytes)
    ord_in_line = cum_ts - 1 - cts_at

    def role(k, value):
        """``value`` at the latest token end with line-ordinal k, and
        whether that token lies in byte i's line: it does iff its
        ordinal passes the count of token starts before the line."""
        got, ordinal = fill(tok_end & (ord_in_line == k), value)
        return got, ordinal > cts_at

    # is the latest newline-or-bad byte strictly before i a bad byte?
    nl_bad = jax.lax.cummax(
        jnp.where(is_nl | is_bad, 2 * idx + 2 + is_bad.astype(I32), 0))
    bad_in_line = jnp.concatenate(
        [jnp.zeros((1,), bool), (nl_bad[:-1] & 1) == 1])

    src, _ = role(0, tok_val)
    dst, has_dst = role(1, tok_val)
    owned = (idx >= owned_start) & (idx < owned_end)
    # ">= 2 tokens in the line" <=> a role-1 token ends inside it
    valid = is_nl & owned & has_dst & ~bad_in_line

    w = None
    if weighted:
        # a minus / a dot in my token: its token ordinal is mine
        neg = jax.lax.cummax(jnp.where(is_minus, cum_ts, 0)) == cum_ts
        has_dot = jax.lax.cummax(jnp.where(is_dot, cum_ts, 0)) == cum_ts
        dig_at_dot = jax.lax.cummax(jnp.where(is_dot, cum_dig, 0))
        frac_len = jnp.where(has_dot, cum_dig - dig_at_dot, 0)
        wf = tok_val.astype(jnp.float32) / _pow10(
            jnp.clip(frac_len, 0, max_digits), max_digits, jnp.float32)
        wf, has_w = role(2, jnp.where(neg, -wf, wf))
        w = jnp.where(has_w, wf, 1.0)          # missing weight -> 1
    return valid, src - base, dst - base, w


def _fold(x, init):
    """``(nb, blen)`` -> ``(nb, ceil(blen / MIN_LINE_BYTES))``: the max of
    each aligned group of ``MIN_LINE_BYTES`` bytes of a block, a block's
    ragged end padded with ``init``.  A ``reduce_window``, since for a
    TPU v5e a reshape to a minor axis of 4 compiles with that axis padded
    to 128 lanes (135 MB of temporaries a batch), and strided slices
    compile to gathers."""
    k = MIN_LINE_BYTES
    return jax.lax.reduce_window(
        x, jnp.asarray(init, x.dtype), jax.lax.max, (1, k), (1, k),
        ((0, 0), (0, -x.shape[1] % k)))


def _compact_accumulate(acc_src, acc_dst, acc_w, total, valid, src, dst, w,
                        *, edge_bound: int):
    """Pack a batch of per-byte parses into the accumulators at ``total``.

    ``valid``/``src``/``dst``/``w`` are ``(nb, blen)`` byte-domain
    outputs of :func:`_parse_block_bytes` (or the Pallas kernel's
    byte-domain realization of it — ``kernels.parse_edges`` fuses the
    same compaction after its kernel).  Blocks pack consecutively and
    edges within a block stay in line order — the same edge order the
    two-step parse_blocks + accumulate pipeline produced.

    Valid bytes of a block lie at least ``MIN_LINE_BYTES`` apart, so each
    aligned group of that many bytes holds at most one, and a group's max
    over its valid bytes is that byte's value, exactly.  The compaction
    runs over the folded groups, a quarter of the bytes, and scatters the
    values themselves: on a TPU v5e a scatter costs 5-6 ns an update,
    dropped or not, and a gather 7-15 ns an index, so two scatters of a
    quarter of the batch cost less than one over its bytes plus gathers
    at every slot (0.68 s against 2.35 s a Graph500 scale-20 text load).
    """
    g = _fold(valid, False).reshape(-1)
    dest = jnp.cumsum(g.astype(I32)) - 1
    count = jnp.maximum(dest[-1] + 1, 0)
    at = jnp.where(g, dest, edge_bound)
    int_min = jnp.iinfo(np.int32).min

    def pack(x, pad):
        """Each valid byte's ``x`` at its edge's slot, ``pad`` elsewhere;
        float values fold as their int32 bits, so they pack bit for bit."""
        bits = jax.lax.bitcast_convert_type(x, I32)
        folded = _fold(jnp.where(valid, bits, int_min), int_min).reshape(-1)
        values = jax.lax.bitcast_convert_type(folded, x.dtype)
        return jnp.full((edge_bound,), pad, x.dtype).at[at].set(
            values, mode="drop")

    # a fixed-size window written at the running offset: with donation
    # this lowers to an in-place memcpy of edge_bound elements; invalid
    # window slots carry the accumulator's padding values, and the next
    # batch's window starts where this batch's edges end, so padding
    # never buries an edge
    acc_src = jax.lax.dynamic_update_slice(acc_src, pack(src, -1), (total,))
    acc_dst = jax.lax.dynamic_update_slice(acc_dst, pack(dst, -1), (total,))
    if acc_w is not None and w is not None:
        acc_w = jax.lax.dynamic_update_slice(acc_w, pack(w, 0.0), (total,))
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
