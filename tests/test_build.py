"""The staged CSR build vs the numpy oracle."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import build, degrees
from repro.core.types import EdgeList
from repro.core.csr import convert_to_csr


def _random_edges(v, e, seed=0, pad=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    if pad:
        src = np.concatenate([src, np.full(pad, -1, np.int32)])
        dst = np.concatenate([dst, np.full(pad, -1, np.int32)])
    return src, dst


def _rows(offsets, targets, v):
    off = np.asarray(offsets)
    tgt = np.asarray(targets)
    return [np.sort(tgt[off[u]:off[u + 1]]) for u in range(v)]


def test_staged_handles_padding_sentinels():
    v = 32
    src, dst = _random_edges(v, 100, seed=3, pad=28)
    ref = build.csr_np(src, dst, None, v)
    o, t, _ = build.csr_staged(jnp.asarray(src), jnp.asarray(dst), None, v,
                               rho=4)
    assert int(o[-1]) == 100
    r_ref = _rows(ref.offsets, ref.targets, v)
    r = _rows(o, t, v)
    for u in range(v):
        assert np.array_equal(r[u], r_ref[u])


def test_weighted_csr_keeps_edge_weight_pairing():
    v, e = 16, 200
    rng = np.random.default_rng(1)
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    w = rng.random(e).astype(np.float32)
    o, t, ww = build.csr_staged(jnp.asarray(src), jnp.asarray(dst),
                                jnp.asarray(w), v, rho=4, weighted=True)
    # every (target, weight) pair within a row must be an original edge pair
    pairs = {(int(u), int(vv), float(x)) for u, vv, x in zip(src, dst, w)}
    off = np.asarray(o)
    for u in range(v):
        for j in range(off[u], off[u + 1]):
            assert (u, int(t[j]), float(np.asarray(ww)[j])) in pairs


def test_degree_strategies_agree():
    v, e = 128, 5000
    src, _ = _random_edges(v, e, seed=9, pad=17)
    ref = degrees.degrees_np(src, v)
    a = degrees.degrees_global(jnp.asarray(src), v)
    b = degrees.combine_degrees(degrees.degrees_partitioned(jnp.asarray(src), v, 4))
    c = degrees.degrees_sort(jnp.asarray(src), v)
    for x in (a, b, c):
        assert np.array_equal(np.asarray(x), ref)


def test_offsets_from_degrees():
    deg = jnp.asarray([3, 0, 2, 5], jnp.int32)
    off = degrees.offsets_from_degrees(deg, 4)
    assert np.asarray(off).tolist() == [0, 3, 3, 5, 10]


def test_convert_to_csr_engines_match():
    v, e = 48, 400
    src, dst = _random_edges(v, e, seed=5)
    el = EdgeList(src, dst, None, np.int64(e), v)
    a = convert_to_csr(el)
    b = convert_to_csr(el, engine="numpy")
    assert np.array_equal(np.asarray(a.offsets, np.int64),
                          np.asarray(b.offsets))
    ra, rb = _rows(a.offsets, a.targets, v), _rows(b.offsets, b.targets, v)
    for u in range(v):
        assert np.array_equal(ra[u], rb[u])


# ---- int32 offsets contract --------------------------------------------------
#
# The device build accumulates offsets as int32 (jnp.cumsum(deg, int32)): at
# E >= 2**31 the cumsum would wrap silently.  The guard must refuse
# loudly at trace time.  Exercised with a mocked limit — the check reads
# the module global, so a 2B-edge graph is not needed.

def test_offsets_width_guard_mocked_limit(monkeypatch):
    monkeypatch.setattr(build, "INT32_OFFSETS_LIMIT", 100)
    v = 16
    src, dst = _random_edges(v, 129, seed=2)
    js, jd = jnp.asarray(src), jnp.asarray(dst)
    with pytest.raises(ValueError, match="exceeds int32 offsets"):
        build.csr_staged(js, jd, None, v)


def test_offsets_width_guard_under_limit_ok(monkeypatch):
    monkeypatch.setattr(build, "INT32_OFFSETS_LIMIT", 150)
    v = 16
    src, dst = _random_edges(v, 130, seed=2)
    o, t, _ = build.csr_staged(jnp.asarray(src), jnp.asarray(dst), None, v)
    assert int(o[-1]) == 130


# ---- staged build, exact order -------------------------------------------------
#
# csr_staged's stable per-partition sort keeps each row's targets in file
# order, so offsets, targets and weights must equal the stable-sort oracle
# element for element — rows are not sorted before comparing.

STAGED_CASES = ["interleaved_padding", "hub", "v1", "e_not_divisible",
                "e_below_rho", "empty_end_rows", "weighted", "random",
                "empty", "single_edge"]


def _weights_for(src, seed=0):
    rng = np.random.default_rng(seed)
    return rng.random(len(src)).astype(np.float32)


def _staged_case(case):
    rng = np.random.default_rng(STAGED_CASES.index(case))
    if case == "interleaved_padding":    # -1 slots scattered, as the exchange gives
        v, e = 50, 1001
        src = rng.integers(0, v, e).astype(np.int32)
        dst = rng.integers(0, v, e).astype(np.int32)
        hole = rng.random(e) < 0.2
        src[hole] = -1
        dst[hole] = -1
    elif case == "hub":                  # every edge on one vertex
        v, e = 32, 257
        src = np.full(e, 7, np.int32)
        dst = rng.integers(0, v, e).astype(np.int32)
    elif case == "v1":
        v, e = 1, 9
        src = np.zeros(e, np.int32)
        dst = np.zeros(e, np.int32)
    elif case == "e_not_divisible":      # E % rho != 0 for every rho below
        v, e = 7, 1001
        src = rng.integers(0, v, e).astype(np.int32)
        dst = rng.integers(0, v, e).astype(np.int32)
    elif case == "e_below_rho":
        v, e = 5, 3
        src = np.asarray([4, 0, 4], np.int32)
        dst = np.asarray([1, 2, 3], np.int32)
    elif case == "empty_end_rows":       # no edge leaves vertex 0 or V-1
        v, e = 100, 4096
        src = rng.integers(1, v - 1, e).astype(np.int32)
        dst = rng.integers(0, v, e).astype(np.int32)
    elif case == "weighted":             # weighted, with padding at the tail
        v, e = 64, 1000
        src, dst = _random_edges(v, e, seed=17, pad=24)
    elif case == "random":
        v, e = 64, 1000
        src, dst = _random_edges(v, e, seed=1)
    elif case == "empty":
        v, src, dst = 8, np.empty(0, np.int32), np.empty(0, np.int32)
    else:                                # single edge
        v, src, dst = 4, np.asarray([2], np.int32), np.asarray([1], np.int32)
    w = _weights_for(src, seed=len(src)) if case == "weighted" else None
    return v, src, dst, w


@pytest.mark.parametrize("rho", [1, 2, 4, 7, 8])
@pytest.mark.parametrize("case", STAGED_CASES)
def test_staged_exact_order_matches_oracle(case, rho):
    v, src, dst, w = _staged_case(case)
    ref = build.csr_np(src, dst, w, v)
    o, t, ww = build.csr_staged(
        jnp.asarray(src), jnp.asarray(dst),
        None if w is None else jnp.asarray(w), v,
        rho=rho, weighted=w is not None)
    n = len(ref.targets)
    assert np.array_equal(np.asarray(o, np.int64), np.asarray(ref.offsets))
    assert np.array_equal(np.asarray(t)[:n], np.asarray(ref.targets))
    assert np.all(np.asarray(t)[n:] == -1)    # padding slots stay unfilled
    if case == "empty_end_rows":
        assert ref.offsets[1] == 0 and ref.offsets[-2] == ref.offsets[-1]
    if w is not None:
        assert np.array_equal(np.asarray(ww)[:n], np.asarray(ref.weights))
