"""Data pipeline: walks over GVEL CSR, prefetcher, determinism."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.configs import reduced_config
from repro.core import convert_to_csr, make_graph_file, read_edgelist_numpy
from repro.data.pipeline import Prefetcher
from repro.data.walks import random_walks, walk_batch

CFG = reduced_config("phi4-mini-3.8b")


@pytest.fixture(scope="module")
def csr(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("g") / "g.el")
    v, e = make_graph_file(path, "rmat", scale=8, edge_factor=8, seed=11)
    el = read_edgelist_numpy(path, num_vertices=v)
    return convert_to_csr(el)


def test_walks_follow_edges(csr):
    import jax
    off = jnp.asarray(np.asarray(csr.offsets), jnp.int32)
    tgt = jnp.asarray(csr.targets)
    walks = random_walks(off, tgt, jax.random.key(0), num_walks=16,
                         length=12, num_vertices=csr.num_vertices)
    w = np.asarray(walks)
    offs = np.asarray(csr.offsets)
    tgts = np.asarray(csr.targets)
    edges_ok = self_loops = 0
    for row in w:
        for a, b in zip(row[:-1], row[1:]):
            nbrs = tgts[offs[a]:offs[a + 1]]
            if b in nbrs:
                edges_ok += 1
            else:                          # self-loop only at dead ends
                assert len(nbrs) == 0 and b == a
                self_loops += 1
    assert edges_ok > 0


def test_walk_batch_shape_and_determinism(csr):
    b1 = walk_batch(csr, CFG, 4, 16, step=3)
    b2 = walk_batch(csr, CFG, 4, 16, step=3)
    b3 = walk_batch(csr, CFG, 4, 16, step=4)
    assert b1["tokens"].shape == (4, 16)
    assert (np.asarray(b1["tokens"]) < CFG.vocab_size).all()
    assert np.array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))
    # labels are next-token shifted
    assert np.array_equal(np.asarray(b1["labels"][:, :-1]),
                          np.asarray(b1["tokens"][:, 1:]))


def test_prefetcher_orders_steps():
    seen = []

    def source(step):
        seen.append(step)
        return {"x": np.full((2,), step)}

    pf = Prefetcher(source, start_step=0, lookahead=2)
    try:
        for i in range(5):
            b = pf.get(expect_step=i)
            assert int(np.asarray(b["x"])[0]) == i
    finally:
        pf.close()


def test_prefetcher_desync_raises():
    pf = Prefetcher(lambda s: {"x": np.zeros(1)}, start_step=3)
    try:
        with pytest.raises(RuntimeError):
            pf.get(expect_step=99)
    finally:
        pf.close()
