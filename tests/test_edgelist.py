"""Reader integration: all engines agree on real files; MTX honored."""
import os

import numpy as np
import pytest

from repro.core import (baselines, convert_to_csr, make_graph_file, read_csr,
                        read_edgelist, read_edgelist_numpy, read_mtx,
                        read_mtx_csr, symmetrize, write_mtx)


@pytest.fixture(scope="module")
def graph_file(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("g") / "g.el")
    v, e = make_graph_file(path, "rmat", scale=9, edge_factor=8, seed=7)
    return path, v, e


def _keyset(el):
    n = int(el.num_edges)
    return sorted(zip(np.asarray(el.src[:n]).tolist(),
                      np.asarray(el.dst[:n]).tolist()))


def test_all_readers_agree(graph_file):
    path, v, e = graph_file
    els = {
        "jax": read_edgelist(path, num_vertices=v, beta=8 * 1024),
        "numpy": read_edgelist_numpy(path, num_vertices=v, num_chunks=3),
        "naive": baselines.read_edgelist_naive(path, num_vertices=v),
        "loadtxt": baselines.read_edgelist_loadtxt(path, num_vertices=v),
        "pigo": baselines.read_edgelist_pigo(path, num_vertices=v),
    }
    ref = _keyset(els["naive"])
    for name, el in els.items():
        assert int(el.num_edges) == e, name
        assert _keyset(el) == ref, name


@pytest.mark.parametrize("beta", [4 * 1024, 64 * 1024])
def test_jax_reader_block_size_invariance(graph_file, beta):
    path, v, e = graph_file
    el = read_edgelist(path, num_vertices=v, beta=beta, batch_blocks=3)
    assert int(el.num_edges) == e


def test_read_csr_matches_pigo_csr(graph_file):
    path, v, e = graph_file
    csr = read_csr(path, num_vertices=v)
    el = baselines.read_edgelist_pigo(path, num_vertices=v)
    ref = baselines.csr_pigo(el)
    assert np.array_equal(np.asarray(csr.offsets, np.int64),
                          np.asarray(ref.offsets))
    off = np.asarray(ref.offsets)
    for u in range(0, v, 37):
        assert np.array_equal(np.sort(np.asarray(csr.targets[off[u]:off[u + 1]])),
                              np.sort(np.asarray(ref.targets[off[u]:off[u + 1]])))


def test_symmetrize_doubles_edges(graph_file):
    path, v, e = graph_file
    el = read_edgelist_numpy(path, num_vertices=v, symmetric=True)
    assert int(el.num_edges) == 2 * e


def test_weighted_file(tmp_path):
    from repro.core.generate import write_edgelist
    rng = np.random.default_rng(0)
    src = rng.integers(0, 50, 200)
    dst = rng.integers(0, 50, 200)
    w = rng.random(200).astype(np.float32)
    path = str(tmp_path / "w.el")
    write_edgelist(path, src, dst, w)
    el = read_edgelist_numpy(path, weighted=True, num_vertices=50)
    assert int(el.num_edges) == 200
    order = np.lexsort((np.asarray(el.dst[:200]), np.asarray(el.src[:200])))
    ro = np.lexsort((dst, src))
    np.testing.assert_allclose(np.asarray(el.weights[:200])[order],
                               w[ro], atol=1e-4)


def test_mtx_attrs_honored(tmp_path):
    """The PIGO bug the paper calls out: symmetric MTX must materialize
    reverse edges; pattern MTX has no weights."""
    path = str(tmp_path / "g.mtx")
    src = np.array([0, 1, 2])
    dst = np.array([1, 2, 0])
    write_mtx(path, src, dst, None, num_vertices=3, symmetric=True)
    el = read_mtx(path)
    assert int(el.num_edges) == 6
    assert el.weights is None
    csr = read_mtx_csr(path)
    deg = np.diff(np.asarray(csr.offsets))
    assert deg.tolist() == [2, 2, 2]


def test_mtx_header_validation(tmp_path):
    path = str(tmp_path / "bad.mtx")
    with open(path, "w") as f:
        f.write("not a matrix market file\n1 2\n")
    with pytest.raises(ValueError):
        read_mtx(path)


def test_mtx_entry_count_check(tmp_path):
    path = str(tmp_path / "trunc.mtx")
    with open(path, "w") as f:
        f.write("%%MatrixMarket matrix coordinate pattern general\n")
        f.write("3 3 5\n1 2\n2 3\n")     # claims 5, has 2
    with pytest.raises(ValueError):
        read_mtx(path)
