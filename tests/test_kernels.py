"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import (csr_offsets, degree_histogram, degree_histogram_ref,
                           exclusive_scan, exclusive_scan_ref, neighbor_gather,
                           neighbor_gather_ref, parse_edges, parse_edges_ref)

# hypothesis is optional: the parametrized sweeps must run everywhere, only
# the property-based sweeps skip when it is absent.
try:
    from hypothesis import given, settings, strategies as st
    settings.register_profile("kern", max_examples=25, deadline=None)
    settings.load_profile("kern")
except ImportError:
    def given(*_a, **_k):
        return pytest.mark.skip(reason="hypothesis not installed")

    class st:  # placeholder strategies so decorators evaluate
        integers = sampled_from = booleans = lists = staticmethod(
            lambda *a, **k: None)


# ---- parse_edges --------------------------------------------------------------

def _mk_bufs(num_blocks, n, seed, weighted=False):
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(num_blocks):
        lines = []
        size = 0
        while size < n - 24:
            if weighted:
                ln = f"{rng.integers(1, 10**6)} {rng.integers(1, 10**6)} " \
                     f"{rng.random():.4f}"
            else:
                ln = f"{rng.integers(1, 10**6)} {rng.integers(1, 10**6)}"
            lines.append(ln)
            size += len(ln) + 1
        buf = ("\n".join(lines) + "\n").encode()
        row = np.full(n, 10, np.uint8)
        row[:len(buf)] = np.frombuffer(buf, np.uint8)[:n]
        rows.append(row)
    return jnp.asarray(np.stack(rows))


@pytest.mark.parametrize("num_blocks,buf_len,weighted", [
    (1, 256, False), (3, 512, False), (2, 1024, True), (4, 256, True),
])
def test_parse_edges_kernel_vs_ref(num_blocks, buf_len, weighted):
    bufs = _mk_bufs(num_blocks, buf_len, seed=buf_len + num_blocks, weighted=weighted)
    cap = buf_len // 4 + 2
    k = parse_edges(bufs, 0, buf_len, weighted=weighted, edge_cap=cap)
    owned = jnp.asarray([0, buf_len], jnp.int32)
    r = parse_edges_ref(bufs, owned, weighted=weighted, base=1, edge_cap=cap)
    assert np.array_equal(np.asarray(k[3]), np.asarray(r[3]))   # counts
    assert np.array_equal(np.asarray(k[0]), np.asarray(r[0]))   # src
    assert np.array_equal(np.asarray(k[1]), np.asarray(r[1]))   # dst
    if weighted:
        np.testing.assert_allclose(np.asarray(k[2]), np.asarray(r[2]),
                                   rtol=1e-5)


@given(st.integers(1, 4), st.sampled_from([128, 256, 512]),
       st.booleans(), st.integers(0, 10**6))
def test_parse_edges_hypothesis(nb, n, weighted, seed):
    bufs = _mk_bufs(nb, n, seed, weighted)
    cap = n // 4 + 2
    k = parse_edges(bufs, 0, n, weighted=weighted, edge_cap=cap)
    owned = jnp.asarray([0, n], jnp.int32)
    r = parse_edges_ref(bufs, owned, weighted=weighted, base=1, edge_cap=cap)
    assert np.array_equal(np.asarray(k[0]), np.asarray(r[0]))
    assert np.array_equal(np.asarray(k[3]), np.asarray(r[3]))


# ---- parse_edges_accumulate (fused pallas-engine path) -----------------------

@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_parse_edges_accumulate_matches_core(weighted, use_kernel):
    """The fused kernel path must match ``core.parse.parse_accumulate``
    bit for bit — same per-byte values, same shared compaction."""
    from repro.core.parse import make_accumulators, parse_accumulate
    from repro.kernels import parse_edges_accumulate

    nb, n = 3, 512
    bufs = _mk_bufs(nb, n, seed=7, weighted=weighted)
    cap = nb * (n // 4 + 2)
    bound = nb * (n // 4 + 2)
    os_, oe = jnp.full((nb,), 0, jnp.int32), jnp.full((nb,), n, jnp.int32)

    ref = make_accumulators(cap, weighted=weighted)
    ref = parse_accumulate(*ref, bufs, os_, oe, weighted=weighted, base=1,
                           edge_bound=bound, donate=False)
    got = make_accumulators(cap, weighted=weighted)
    got = parse_edges_accumulate(*got, bufs, 0, n, weighted=weighted, base=1,
                                 edge_bound=bound, use_kernel=use_kernel,
                                 donate=False)
    assert int(got[3]) == int(ref[3])
    assert np.array_equal(np.asarray(got[0]), np.asarray(ref[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(ref[1]))
    if weighted:
        assert np.array_equal(np.asarray(got[2]), np.asarray(ref[2]))


def test_parse_edges_accumulate_packs_across_batches():
    from repro.core.parse import make_accumulators
    from repro.kernels import parse_edges_accumulate

    def pad(text, n=64):
        row = np.full(n, 10, np.uint8)
        b = np.frombuffer(text, np.uint8)
        row[:len(b)] = b
        return row

    acc = make_accumulators(16, weighted=False)
    acc = parse_edges_accumulate(
        *acc, jnp.asarray(np.stack([pad(b"1 2\n3 4\n"), pad(b"5 6\n")])),
        0, 64, weighted=False, base=1, edge_bound=8, donate=False)
    acc = parse_edges_accumulate(
        *acc, jnp.asarray(np.stack([pad(b"7 8\n")])), 0, 64,
        weighted=False, base=1, edge_bound=8, donate=False)
    assert int(acc[3]) == 4
    assert np.asarray(acc[0]).tolist() == [0, 2, 4, 6] + [-1] * 12
    assert np.asarray(acc[1]).tolist() == [1, 3, 5, 7] + [-1] * 12


# ---- degree_histogram ----------------------------------------------------------

@pytest.mark.parametrize("v,e,eblk,vt", [
    (100, 1000, 128, 64), (513, 2047, 256, 128), (64, 64, 512, 512),
])
def test_degree_histogram_sweep(v, e, eblk, vt):
    rng = np.random.default_rng(v + e)
    src = rng.integers(0, v, e).astype(np.int32)
    src[::11] = -1
    got = degree_histogram(jnp.asarray(src), num_vertices=v, e_blk=eblk, vt=vt)
    ref = degree_histogram_ref(jnp.asarray(src), num_vertices=v)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


@given(st.integers(2, 300), st.integers(0, 2000), st.integers(0, 99))
def test_degree_histogram_hypothesis(v, e, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, e).astype(np.int32)
    got = degree_histogram(jnp.asarray(src), num_vertices=v, e_blk=256, vt=128)
    assert np.array_equal(np.asarray(got),
                          np.bincount(src, minlength=v).astype(np.int32))


# ---- exclusive_scan -------------------------------------------------------------

@pytest.mark.parametrize("n,blk", [(10, 16), (1024, 128), (1000, 256),
                                   (4097, 512)])
def test_exclusive_scan_sweep(n, blk):
    rng = np.random.default_rng(n)
    x = rng.integers(0, 50, n).astype(np.int32)
    got, tot = exclusive_scan(jnp.asarray(x), blk=blk)
    ref, rtot = exclusive_scan_ref(jnp.asarray(x))
    assert np.array_equal(np.asarray(got), np.asarray(ref))
    assert int(tot) == int(rtot)


def test_csr_offsets_shape():
    deg = jnp.asarray([2, 0, 3], jnp.int32)
    off = csr_offsets(deg, blk=16)
    assert np.asarray(off).tolist() == [0, 2, 2, 5]


@given(st.lists(st.integers(0, 100), min_size=1, max_size=500))
def test_exclusive_scan_hypothesis(xs):
    x = np.asarray(xs, np.int32)
    got, tot = exclusive_scan(jnp.asarray(x), blk=64)
    assert np.array_equal(np.asarray(got), np.cumsum(x) - x)
    assert int(tot) == int(x.sum())


# ---- neighbor_gather -------------------------------------------------------------

@pytest.mark.parametrize("v,e,width,bt", [(20, 100, 16, 8), (50, 500, 32, 16),
                                          (5, 40, 64, 4)])
def test_neighbor_gather_sweep(v, e, width, bt):
    rng = np.random.default_rng(v * e)
    src = np.sort(rng.integers(0, v, e)).astype(np.int32)
    deg = np.bincount(src, minlength=v)
    offsets = np.zeros(v + 1, np.int32)
    np.cumsum(deg, out=offsets[1:])
    targets = rng.integers(0, v, e).astype(np.int32)
    verts = rng.integers(0, v, 3 * bt).astype(np.int32)
    got = neighbor_gather(jnp.asarray(verts), jnp.asarray(offsets),
                          jnp.asarray(targets), width=width, bt=bt)
    ref = neighbor_gather_ref(jnp.asarray(verts), jnp.asarray(offsets),
                              jnp.asarray(targets), width=width)
    assert np.array_equal(np.asarray(got[0]), np.asarray(ref[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(ref[1]))
    # semantic check: rows match the CSR
    for i, u in enumerate(verts):
        row = targets[offsets[u]:offsets[u + 1]][:width]
        assert np.asarray(got[0][i][:len(row)]).tolist() == row.tolist()
