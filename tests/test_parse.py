"""Unit tests: vectorized parsers (jnp device path + numpy host path)."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import blocks
from repro.core.loader import DEFAULT_OVERLAP
from repro.core.parse import (_parse_block_bytes, make_accumulators,
                              parse_accumulate, parse_block, parse_blocks)
from repro.core.parse_np import chunk_bounds, parse_chunk_np


def _pad(text: bytes, mult: int = 64) -> np.ndarray:
    buf = np.frombuffer(text, np.uint8)
    pad = (-len(buf)) % mult
    return np.concatenate([buf, np.full(pad, 10, np.uint8)])


ALLOWED = set(b"0123456789.- \t\r")


def _oracle(text: bytes, weighted=False, base=1):
    src, dst, w = [], [], []
    for line in text.split(b"\n"):
        # GVEL semantics: any line with a byte outside the edge grammar
        # (comments, junk) is rejected wholesale
        if any(c not in ALLOWED for c in line):
            continue
        parts = line.split()
        if len(parts) < 2:
            continue
        src.append(int(parts[0]) - base)
        dst.append(int(parts[1]) - base)
        w.append(float(parts[2]) if weighted and len(parts) > 2 else 1.0)
    return src, dst, w


CASES = [
    b"1 2\n3 4\n",
    b"1 2\n\n\n3 4\n",                      # blank lines
    b"10 20\n% comment 5 5\n30 40\n",       # comment rejected
    b"1\t2\n3  4\n5 6",                     # tabs, multi-space, no trailing nl
    b"999999999 1\n1 999999999\n",          # 9-digit ids
    b"1 2 extra tokens 3\n",                # extra junk -> bad line
    b"5\n1 2\n7\n\n8 9\n",                   # 1-token lines: no role-0 leak
    # a line of exactly `overlap` bytes, leading and trailing whitespace
    b"  12 34".ljust(DEFAULT_OVERLAP - 2) + b"\t\n",
    b"1 2\r\n3 4\r\n",                       # \r\n endings
    b"007 08\n",                            # leading zeros
    # 64 bytes: the last line ends on the (unpadded) block's last byte
    b"1 2\n" + b"123456789".ljust(58) + b"1\n",
    b"123456789 5\n6 100000000\n",          # 9-digit ids beside 1-digit ids
]


@pytest.mark.parametrize("text", CASES)
def test_parse_block_matches_oracle(text):
    buf = _pad(text)
    s, d, w, c = parse_block(jnp.asarray(buf), jnp.int32(0),
                             jnp.int32(len(buf)), weighted=False, base=1,
                             edge_cap=32)
    es, ed, _ = _oracle(text)
    assert int(c) == len(es)
    assert np.asarray(s[:len(es)]).tolist() == es
    assert np.asarray(d[:len(ed)]).tolist() == ed


@pytest.mark.parametrize("text", CASES)
def test_parse_np_matches_oracle(text):
    s, d, w, c = parse_chunk_np(np.frombuffer(text, np.uint8), weighted=False)
    es, ed, _ = _oracle(text)
    assert c == len(es)
    assert s.tolist() == es and d.tolist() == ed


def test_weighted_floats():
    text = b"1 2 0.5\n2 3 -1.25\n3 4 7\n4 5 12.0625\n"
    buf = _pad(text)
    s, d, w, c = parse_block(jnp.asarray(buf), jnp.int32(0),
                             jnp.int32(len(buf)), weighted=True, base=1,
                             edge_cap=16)
    assert int(c) == 4
    np.testing.assert_allclose(np.asarray(w[:4]), [0.5, -1.25, 7.0, 12.0625],
                               rtol=1e-6)
    s2, d2, w2, c2 = parse_chunk_np(np.frombuffer(text, np.uint8),
                                    weighted=True)
    np.testing.assert_allclose(w2, [0.5, -1.25, 7.0, 12.0625], rtol=1e-12)


def test_missing_weight_defaults_to_one():
    text = b"1 2\n2 3 4.5\n"
    buf = _pad(text)
    s, d, w, c = parse_block(jnp.asarray(buf), jnp.int32(0),
                             jnp.int32(len(buf)), weighted=True, base=1,
                             edge_cap=8)
    np.testing.assert_allclose(np.asarray(w[:2]), [1.0, 4.5])


def test_zero_based_ids():
    text = b"0 1\n1 2\n"
    buf = _pad(text)
    s, d, _, c = parse_block(jnp.asarray(buf), jnp.int32(0),
                             jnp.int32(len(buf)), weighted=False, base=0,
                             edge_cap=8)
    assert np.asarray(s[:2]).tolist() == [0, 1]


def test_ownership_partition_is_exact():
    """Every line owned by exactly one block for any beta."""
    rng = np.random.default_rng(0)
    lines = [f"{rng.integers(1, 99)} {rng.integers(1, 99)}" for _ in range(200)]
    text = ("\n".join(lines) + "\n").encode()
    data = np.frombuffer(text, np.uint8)
    for beta in (16, 64, 256):
        ov = 32
        total = 0
        nb = -(-len(data) // beta)
        for i in range(nb):
            lo = i * beta - ov
            buf = np.full(ov + beta, 10, np.uint8)
            s, e = max(lo, 0), min(i * beta + beta, len(data))
            buf[s - lo:e - lo] = data[s:e]
            _, _, _, c = parse_block(jnp.asarray(buf), jnp.int32(ov),
                                     jnp.int32(ov + beta), weighted=False,
                                     base=1, edge_cap=ov + beta)
            total += int(c)
        assert total == 200, beta


def test_parse_accumulate_packs_batches():
    """The fused step packs each batch's edges contiguously at the
    running offset, leaving -1 padding past the total."""
    bufs = jnp.asarray(np.stack([_pad(b"1 2\n3 4\n"), _pad(b"5 6\n")]))
    os_ = jnp.zeros(2, jnp.int32)
    oe = jnp.full(2, bufs.shape[1], jnp.int32)
    acc_s = jnp.full((16,), -1, jnp.int32)
    acc_d = jnp.full((16,), -1, jnp.int32)
    tot = jnp.zeros((), jnp.int32)
    acc_s, acc_d, _, tot = parse_accumulate(
        acc_s, acc_d, None, tot, bufs, os_, oe, weighted=False, base=1,
        edge_bound=8, donate=False)
    # second batch lands after the first batch's edges
    acc_s, acc_d, _, tot = parse_accumulate(
        acc_s, acc_d, None, tot, jnp.asarray(np.stack([_pad(b"7 8\n")])),
        os_[:1], oe[:1], weighted=False, base=1, edge_bound=8, donate=False)
    assert int(tot) == 4
    assert np.asarray(acc_s).tolist() == [0, 2, 4, 6] + [-1] * 12
    assert np.asarray(acc_d).tolist() == [1, 3, 5, 7] + [-1] * 12


def test_chunk_bounds_newline_aligned():
    text = b"11 22\n33 44\n55 66\n77 88\n"
    data = np.frombuffer(text, np.uint8)
    bounds = chunk_bounds(data, 3)
    assert bounds[0][0] == 0 and bounds[-1][1] == len(data)
    for lo, hi in bounds[:-1]:
        assert hi == 0 or data[hi - 1] == 10   # cuts at newline


def _messy_text(rng, n_lines: int) -> bytes:
    """Edge lines in every shape the grammar allows (ids of 1-9 digits,
    leading zeros, 3-token lines, signed decimal weights, surrounding
    whitespace, \\r\\n) among blank, whitespace-only, 1-token and junk
    lines; every line fits the loader's overlap."""
    def ws():
        return "".join(rng.choice([" ", "\t"], rng.integers(1, 4)))

    def num():
        return str(rng.integers(0, 10 ** int(rng.integers(1, 10)))).zfill(
            int(rng.integers(1, 10)))

    lines = []
    for _ in range(n_lines):
        kind = rng.integers(0, 10)
        if kind == 0:
            ln = ""
        elif kind == 1:
            ln = ws()
        elif kind == 2:
            ln = num()
        elif kind == 3:
            ln = f"# {num()} {num()}"
        else:
            ln = num() + ws() + num()
            if kind >= 7:
                sign = "-" if rng.random() < 0.3 else ""
                frac = f".{rng.integers(0, 1000):03d}" if kind >= 8 else ""
                ln += ws() + f"{sign}{rng.integers(0, 10**4)}{frac}"
        if rng.random() < 0.3:
            ln = ws() + ln + ws()
        if rng.random() < 0.2:
            ln += "\r"
        lines.append(ln)
    return ("\n".join(lines) + "\n").encode()


@pytest.mark.parametrize("beta,nb", [(256, 3), (1 << 16, 2)])
@pytest.mark.parametrize("weighted", [False, True])
def test_parse_accumulate_messy_batches_match_oracle(beta, nb, weighted):
    """The streaming path over seeded messy text, staged as the loader
    stages it (several multi-block batches), equals the Python oracle.
    At 2^16-byte blocks token ordinals take 16 bits, so each byte-domain
    fill splits a value into three pieces; at 256 bytes, two."""
    rng = np.random.default_rng(beta + nb + weighted)
    text = _messy_text(rng, 3 * nb * beta // 12)
    data = np.frombuffer(text, np.uint8)
    plan = blocks.plan_blocks(len(data), beta, DEFAULT_OVERLAP)
    lo, hi = blocks.owned_range(plan)
    edge_bound = nb * (plan.buf_len // 4 + 2)
    n_batches = -(-plan.num_blocks // nb)
    assert n_batches >= 3
    acc = make_accumulators(n_batches * edge_bound, weighted=weighted)
    for b0 in range(0, plan.num_blocks, nb):
        ids = np.arange(b0, min(b0 + nb, plan.num_blocks))
        bufs = blocks.stage_blocks(data, plan, ids, check_lines=True)
        k = len(ids)
        acc = parse_accumulate(
            *acc, jnp.asarray(bufs), jnp.full((k,), lo, jnp.int32),
            jnp.full((k,), hi, jnp.int32), weighted=weighted, base=1,
            edge_bound=k * (plan.buf_len // 4 + 2), donate=False)
    es, ed, ew = _oracle(text, weighted=weighted)
    total = int(acc[3])
    assert total == len(es)
    assert np.asarray(acc[0][:total]).tolist() == es
    assert np.asarray(acc[1][:total]).tolist() == ed
    if weighted:
        np.testing.assert_allclose(np.asarray(acc[2][:total]), ew, rtol=1e-6)


@pytest.mark.parametrize("n,fits", [(1 << 23, True), (1 << 24, False)])
def test_block_length_bounds_the_fills(n, fits):
    """A fill packs a token ordinal above each piece of a value in one
    int32; a block too long for that is refused, never wrapped."""
    trace = functools.partial(
        jax.eval_shape,
        functools.partial(_parse_block_bytes, weighted=True, base=1),
        jax.ShapeDtypeStruct((n,), jnp.uint8),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
    if fits:
        assert trace()[0].shape == (n,)
    else:
        with pytest.raises(ValueError, match="too long"):
            trace()
