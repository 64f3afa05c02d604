"""Pallas kernel: edgelist text block -> per-byte parsed edges.

GVEL Algorithm 1's hot loop written as a Pallas kernel.  It runs only
in interpret mode: it does not lower for TPU yet.  Compiled for v5e,
Mosaic refuses the ``(1, buf_len)`` block shape when ``nb > 1`` (a
block's last two dims must divide by (8, 128) or equal the array's),
and with ``nb == 1`` it fails on ``cumsum``, which has no Mosaic
lowering; the body's ``cummax``/``cummin`` and dynamic gathers
(``cum_dig[end_pos]``, ``tok_val[p0]``) would come next.  The loaders'
TPU path is the XLA twin, ``core.parse._parse_block_bytes``.  Each grid
step takes one `buf_len`-byte block and runs the mask/scan parse:

  byte classes -> token segmentation (cumsum) -> digit place values
  (sorted-segment algebra: cumulative max/min/sum + gathers) -> per-line
  values pinned at terminating newlines.

The kernel emits the **byte domain**: ``valid[i]`` marks owned newlines
terminating well-formed edge lines, with that line's (src, dst, w) at
those bytes — the same contract as ``core.parse._parse_block_bytes``.
This body looks values up at running max/min positions (gathers); the
twin computes them as gather-free fills.  The two agree at every valid
byte, so they match after compaction.  Compaction is
deliberately *outside* the kernel: the fused loader path packs a whole
batch with one scatter (``core.parse._compact_accumulate``) straight
into the donated accumulators, and the standalone ``parse_edges`` entry
compacts per block.  The body has no scatter, but its scans and
gathers are what Mosaic refuses (above).

`weighted` is a *Python-level* specialization parameter — the paper found
(§4.1.6) that making the weighted flag a template parameter keeps the hot
loop small enough to stay in the instruction cache; here each value of
the flag produces a distinct, smaller program, the same insight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

I32 = jnp.int32


def _parse_bytes_body(owned_ref, buf_ref, valid_ref, src_ref, dst_ref, w_ref,
                      *, weighted: bool, base: int, max_digits: int):
    n = buf_ref.shape[-1]
    d = buf_ref[0, :].astype(I32)
    idx = jax.lax.iota(I32, n)
    owned_start = owned_ref[0]
    owned_end = owned_ref[1]

    is_digit = (d >= 48) & (d <= 57)
    is_dot = d == 46
    is_minus = d == 45
    is_tok = is_digit | is_dot | is_minus
    is_nl = d == 10
    is_ws = (d == 32) | (d == 9) | (d == 13)
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
    pow10_i = 10 ** jax.lax.iota(I32, max_digits + 1)
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

    valid_ref[0, :] = valid.astype(I32)
    src_ref[0, :] = tok_val[jnp.maximum(p0, 0)] - base
    dst_ref[0, :] = tok_val[jnp.maximum(p1, 0)] - base

    if weighted:
        p2 = role_pos(2)
        dot_pos = jax.lax.cummax(jnp.where(is_dot, idx, -1))
        minus_pos = jax.lax.cummax(jnp.where(is_minus, idx, -1))
        p2c = jnp.maximum(p2, 0)
        w_start = start_pos[p2c]
        dot_of = dot_pos[p2c]
        frac_len = jnp.where(dot_of >= w_start,
                             cum_dig[p2c] - cum_dig[jnp.maximum(dot_of, 0)], 0)
        pow10_f = jnp.float32(10.0) ** jax.lax.iota(jnp.float32,
                                                    max_digits + 1)
        wf = tok_val[p2c].astype(jnp.float32) \
            / pow10_f[jnp.clip(frac_len, 0, max_digits)]
        wf = jnp.where(minus_pos[p2c] >= w_start, -wf, wf)
        w_ref[0, :] = jnp.where(p2 > pex, wf, 1.0)   # missing weight -> 1
    else:
        w_ref[0, :] = jnp.ones((n,), jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("weighted", "base", "max_digits", "interpret"),
)
def parse_bytes_kernel(
    bufs: jax.Array,          # (nb, buf_len) uint8
    owned: jax.Array,         # (2,) int32 — [owned_start, owned_end)
    *,
    weighted: bool,
    base: int,
    max_digits: int = 9,
    interpret: bool = True,
):
    """Per-byte parse of a batch of blocks: ``(valid, src, dst, w)``,
    each ``(nb, buf_len)`` (``w`` is None when unweighted).  The
    byte-domain contract of ``core.parse._parse_block_bytes``."""
    nb, buf_len = bufs.shape
    body = functools.partial(_parse_bytes_body, weighted=weighted, base=base,
                             max_digits=max_digits)
    out_shapes = (
        jax.ShapeDtypeStruct((nb, buf_len), I32),           # valid mask
        jax.ShapeDtypeStruct((nb, buf_len), I32),           # src
        jax.ShapeDtypeStruct((nb, buf_len), I32),           # dst
        jax.ShapeDtypeStruct((nb, buf_len), jnp.float32),   # w
    )
    spec = pl.BlockSpec((1, buf_len), lambda i: (i, 0))
    valid, src, dst, w = pl.pallas_call(
        body,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((2,), lambda i: (0,)),   # owned range (scalar-ish)
            spec,
        ],
        out_specs=(spec, spec, spec, spec),
        out_shape=out_shapes,
        interpret=interpret,
    )(owned, bufs)
    return valid != 0, src, dst, (w if weighted else None)


def _compact_block(valid, src_b, dst_b, w_b, *, edge_cap: int,
                   weighted: bool):
    """One block's byte-domain parse -> fixed-capacity (src, dst, w, cnt);
    the single compaction scatter of ``core.parse.parse_block``."""
    n = valid.shape[0]
    pos = jnp.cumsum(valid.astype(I32)) - 1
    cnt = jnp.maximum(pos[-1] + 1, 0)
    packed = jnp.full((edge_cap,), n, I32).at[
        jnp.where(valid, pos, edge_cap)].set(
            jnp.arange(n, dtype=I32), mode="drop")
    pv = packed < n
    pc = jnp.minimum(packed, n - 1)
    src = jnp.where(pv, src_b[pc], -1)
    dst = jnp.where(pv, dst_b[pc], -1)
    w = jnp.where(pv, w_b[pc], 0.0) if weighted else None
    return src, dst, w, cnt


@functools.partial(
    jax.jit,
    static_argnames=("weighted", "base", "edge_cap", "max_digits",
                     "interpret"),
)
def parse_edges_kernel(
    bufs: jax.Array,          # (nb, buf_len) uint8
    owned: jax.Array,         # (2,) int32 — [owned_start, owned_end)
    *,
    weighted: bool,
    base: int,
    edge_cap: int,
    max_digits: int = 9,
    interpret: bool = True,
):
    """Kernel parse + per-block compaction: (src, dst, w, counts), each
    row a fixed-capacity block parse (the historical packed contract)."""
    valid, src, dst, w = parse_bytes_kernel(
        bufs, owned, weighted=weighted, base=base, max_digits=max_digits,
        interpret=interpret)
    fn = functools.partial(_compact_block, edge_cap=edge_cap,
                           weighted=weighted)
    if weighted:
        src_o, dst_o, w_o, cnt = jax.vmap(fn)(valid, src, dst, w)
    else:
        src_o, dst_o, w_o, cnt = jax.vmap(
            lambda v, s, d: fn(v, s, d, None))(valid, src, dst)
    return src_o, dst_o, w_o, cnt
