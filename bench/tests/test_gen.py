"""The benchmark's copies of the program's generators, writers and
reference give what the originals give, and the graph families draw
what their configurations state."""
import json

import numpy as np
import pytest

from bench.gen import graphs, reference, writers
from bench.spec import BENCH, plugin
from repro.core import build, generate, snapshot
from repro.core.types import EdgeList

GRID = {"side": 9, "num_vertices": 81, "num_edges": 4 * 9 * 8,
        "text_bytes": None}


def _graph500(scale, seed):
    return plugin("gen/graphs", "graph500").kronecker(
        scale, 16, a=0.57, b=0.19, c=0.19, seed=seed)


def _grid(seed, **over):
    config = json.loads((BENCH / "configs" / "road-d-grid.json").read_text())
    return graphs.make({**config, **GRID, **over}, seed)


@pytest.mark.parametrize("scale,seed", [(8, 0), (11, 2**31 + 9)])
def test_graph500_matches_rmat_edges(scale, seed):
    got = _graph500(scale, seed)
    want = generate.rmat_edges(scale, 16, seed=seed)
    assert got[2] == want[2]
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_grid_is_a_seeded_relabelling_of_grid_edges_both_ways():
    g = _grid(5)
    s0, d0, v0 = generate.grid_edges(9)
    assert g.num_vertices == v0 and g.num_edges == 2 * len(s0)
    # arcs come in pairs, u v d then v u d
    np.testing.assert_array_equal(g.src[0::2], g.dst[1::2])
    np.testing.assert_array_equal(g.dst[0::2], g.src[1::2])
    np.testing.assert_array_equal(g.weights[0::2], g.weights[1::2])
    assert 1 <= g.weights.min() and g.weights.max() <= 9999
    rng = graphs.rng_for(5)
    rng.permutation(len(s0))
    perm = rng.permutation(v0)
    assert sorted(zip(g.src[0::2], g.dst[0::2])) \
        == sorted(zip(perm[s0], perm[d0]))
    assert not np.array_equal(g.src[0::2], perm[s0])    # segments reordered
    assert not np.array_equal(g.weights, _grid(6).weights)


def test_make_refuses_sizes_the_configuration_does_not_state():
    with pytest.raises(ValueError, match="states"):
        _grid(1, num_edges=7)


def test_text_bytes_equal_write_edgelist(tmp_path):
    src, dst, v = _graph500(9, 3)
    a, b = tmp_path / "a.el", tmp_path / "b.el"
    generate.write_edgelist(str(a), src, dst)
    writers.write_text(str(b), src, dst)
    assert a.read_bytes() == b.read_bytes()


def test_weighted_text_is_u_v_d_lines(tmp_path):
    g = _grid(2**31 + 3)
    p = tmp_path / "g.el"
    writers.write_text(str(p), g.src, g.dst, g.weights)
    want = "".join(f"{u + 1} {v + 1} {d}\n"
                   for u, v, d in zip(g.src, g.dst, g.weights))
    assert p.read_text() == want


def test_text_padding_is_empty_lines(tmp_path):
    p = tmp_path / "p.el"
    n = writers.write_text(str(p), [0, 5], [1, 2], pad_to=20)
    assert n == 20 and p.read_bytes() == b"1 2\n6 3\n" + b"\n" * 12
    with pytest.raises(ValueError):
        writers.write_text(str(p), [0, 5], [1, 2], pad_to=4)


@pytest.mark.parametrize("weighted", [False, True])
def test_gvel_bytes_equal_save_snapshot(tmp_path, weighted):
    g = _grid(1)
    w = g.weights.astype(np.float32) if weighted else None
    a, b = tmp_path / "a.gvel", tmp_path / "b.gvel"
    snapshot.save_snapshot(str(a), edgelist=EdgeList(
        g.src.astype(np.int32), g.dst.astype(np.int32), w,
        np.int64(g.num_edges), g.num_vertices))
    writers.write_gvel(str(b), g.src, g.dst, g.num_vertices, w)
    assert a.read_bytes() == b.read_bytes()


@pytest.mark.parametrize("weighted", [False, True])
def test_reference_equals_csr_np(weighted):
    src, dst, v = _graph500(10, 4)
    w = (np.random.default_rng(4).integers(1, 10**4, len(src))
         .astype(np.float32) if weighted else None)
    got = reference.csr_np(src, dst, w, v)
    want = build.csr_np(src, dst, w, v)
    np.testing.assert_array_equal(got.offsets, want.offsets)
    np.testing.assert_array_equal(got.targets, want.targets)
    if weighted:
        np.testing.assert_array_equal(got.weights, want.weights)
