"""Walk determinism contract: bitwise repeatability, batch-split
invariance, adjacency confinement vs a numpy oracle, isolated-vertex
self-loops (the corpus/serving resume + degrade contracts build on
these — docs/serving.md)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import convert_to_csr, make_graph_file, read_edgelist_numpy
from repro.data.walks import (random_walks, walk_batch, walk_from,
                              walk_keys)


class _Cfg:
    vocab_size = 64


@pytest.fixture(scope="module")
def csr(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("w") / "g.el")
    v, e = make_graph_file(path, "rmat", scale=8, edge_factor=8, seed=11)
    el = read_edgelist_numpy(path, num_vertices=v)
    return convert_to_csr(el)


def _arrays(csr):
    return (jnp.asarray(np.asarray(csr.offsets), jnp.int32),
            jnp.asarray(np.asarray(csr.targets), jnp.int32))


def _assert_confined(walks, offsets, targets):
    """Numpy oracle: every step lands inside the current vertex's
    adjacency; a dead end (out-degree 0) self-loops."""
    offs, tgts = np.asarray(offsets), np.asarray(targets)
    for row in np.asarray(walks):
        for a, b in zip(row[:-1], row[1:]):
            nbrs = tgts[offs[a]:offs[a + 1]]
            if len(nbrs):
                assert b in nbrs, (a, b, nbrs)
            else:
                assert b == a, f"dead end {a} stepped to {b}, not self-loop"


def test_same_key_same_csr_bitwise_identical(csr):
    off, tgt = _arrays(csr)
    k = jax.random.key(7)
    w1 = random_walks(off, tgt, k, num_walks=8, length=12,
                      num_vertices=csr.num_vertices)
    w2 = random_walks(off, tgt, k, num_walks=8, length=12,
                      num_vertices=csr.num_vertices)
    assert np.array_equal(np.asarray(w1), np.asarray(w2))


def test_batch_split_invariance(csr):
    """num_walks=8 equals the concatenation of two num_walks=4 calls at
    walk offsets 0 and 4 — per-walk keying, bitwise."""
    off, tgt = _arrays(csr)
    k = jax.random.key(3)
    kw = dict(length=10, num_vertices=csr.num_vertices)
    full = np.asarray(random_walks(off, tgt, k, num_walks=8, **kw))
    lo = np.asarray(random_walks(off, tgt, k, num_walks=4, walk_offset=0, **kw))
    hi = np.asarray(random_walks(off, tgt, k, num_walks=4, walk_offset=4, **kw))
    assert np.array_equal(full, np.concatenate([lo, hi]))
    # ...and any prefix batch is the prefix of the full batch
    pre = np.asarray(random_walks(off, tgt, k, num_walks=3, **kw))
    assert np.array_equal(full[:3], pre)


def test_walks_confined_random_csrs():
    """Property over random CSRs (isolated vertices included, by
    construction): walks never leave adjacency, dead ends self-loop."""
    rng = np.random.default_rng(0)
    for trial in range(8):
        v = 32
        ne = int(rng.integers(0, 120))
        src = rng.integers(0, v // 2, ne)       # top half stays isolated
        dst = rng.integers(0, v, ne)
        counts = np.bincount(src, minlength=v)
        offsets = np.zeros(v + 1, np.int64)
        np.cumsum(counts, out=offsets[1:])
        targets = dst[np.argsort(src, kind="stable")]
        off = jnp.asarray(offsets, jnp.int32)
        tgt = jnp.asarray(targets, jnp.int32)
        walks = random_walks(off, tgt, jax.random.key(trial), num_walks=8,
                             length=8, num_vertices=v)
        _assert_confined(walks, offsets, targets)


def test_isolated_vertex_self_loops_not_crash():
    # vertex 2 of 4 has no out-edges; a walk pinned there never moves
    offsets = jnp.asarray([0, 1, 2, 2, 3], jnp.int32)
    targets = jnp.asarray([1, 0, 0], jnp.int32)
    w = walk_from(offsets, targets, walk_keys(jax.random.key(0), [0]),
                  [2], length=6)
    assert np.array_equal(np.asarray(w)[0], np.full(6, 2))


def test_edgeless_graph_self_loops():
    offsets = jnp.zeros(6, jnp.int32)
    targets = jnp.zeros((0,), jnp.int32)
    w = np.asarray(random_walks(offsets, targets, jax.random.key(1),
                                num_walks=4, length=5, num_vertices=5))
    assert np.array_equal(w, np.repeat(w[:, :1], 5, axis=1))


def test_walk_from_pins_start(csr):
    off, tgt = _arrays(csr)
    w = walk_from(off, tgt, walk_keys(jax.random.key(2), [9]), [5], length=7)
    assert int(np.asarray(w)[0, 0]) == 5
    _assert_confined(w, csr.offsets, csr.targets)


def test_walk_batch_seeded_and_split_stable(csr):
    b1 = walk_batch(csr, _Cfg, 4, 16, step=3)
    b2 = walk_batch(csr, _Cfg, 4, 16, step=3)
    assert np.array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    # a different seed is a different corpus
    b3 = walk_batch(csr, _Cfg, 4, 16, step=3, seed=1)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))
    # batch split invariance carries through walk_batch
    lo = walk_batch(csr, _Cfg, 2, 16, step=3)
    assert np.array_equal(np.asarray(b1["tokens"])[:2],
                          np.asarray(lo["tokens"]))
