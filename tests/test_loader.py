"""Unified loader layer: registry resolution + engine parity vs the
``csr_np`` host oracle on generated graphs."""
import os

import numpy as np
import pytest

from repro.core import (available_engines, get_engine, load_csr,
                        load_edgelist, register_engine)
from repro.core.build import csr_np
from repro.core.generate import write_edgelist
from repro.core.loader import _REGISTRY

ENGINES = ["device", "numpy", "threads", "pallas"]
# small blocks keep the streaming engines' compiles cheap
SMALL_KW = {"device": dict(beta=4096, batch_blocks=2),
            "pallas": dict(beta=2048, batch_blocks=2)}


# ---- registry ----------------------------------------------------------------

def test_builtin_engines_registered():
    assert set(ENGINES) <= set(available_engines())


def test_get_engine_unknown_lists_available():
    with pytest.raises(ValueError, match="numpy"):
        get_engine("no-such-engine")


def test_register_engine_last_wins_and_dispatches(tmp_path):
    class Fake:
        name = "fake-test-engine"

        def read_edgelist(self, path, **kw):
            from repro.core.types import EdgeList
            return EdgeList(np.array([7], np.int32), np.array([8], np.int32),
                            None, np.int64(1), 9)

    try:
        register_engine(Fake())
        el = load_edgelist("/nonexistent", engine="fake-test-engine")
        assert int(el.num_edges) == 1 and el.num_vertices == 9
    finally:
        _REGISTRY.pop("fake-test-engine", None)


def test_pallas_engine_refuses_tpu_backend(tmp_path, monkeypatch):
    """The Pallas parse kernel does not lower for TPU: on a TPU backend
    the engine fails at its first call instead of running the XLA twin."""
    import jax
    path = tmp_path / "g.el"
    path.write_text("1 2\n2 3\n")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="does not lower for TPU"):
        load_csr(str(path), engine="pallas", beta=2048)


# ---- engine parity vs host oracle -------------------------------------------

def _graph(tmp_path, *, weighted, base, seed=0, v=60, e=400):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, e)
    dst = rng.integers(0, v, e)
    w = (rng.random(e) * 9).round(3).astype(np.float32) if weighted else None
    path = str(tmp_path / f"g_{weighted}_{base}.el")
    write_edgelist(path, src, dst, w, base=base)
    oracle = csr_np(src.astype(np.int32), dst.astype(np.int32), w, v)
    return path, v, e, oracle


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("weighted,base", [(False, 1), (False, 0),
                                           (True, 1), (True, 0)])
def test_load_csr_matches_oracle(tmp_path, engine, weighted, base):
    path, v, e, oracle = _graph(tmp_path, weighted=weighted, base=base,
                                seed=base + 2 * weighted)
    csr = load_csr(path, engine=engine, weighted=weighted, base=base,
                   num_vertices=v, **SMALL_KW.get(engine, {}))
    assert np.array_equal(np.asarray(csr.offsets, np.int64),
                          np.asarray(oracle.offsets))
    off = np.asarray(oracle.offsets)
    for u in range(v):
        mine = np.sort(np.asarray(csr.targets[off[u]:off[u + 1]]))
        ref = np.sort(np.asarray(oracle.targets[off[u]:off[u + 1]]))
        assert np.array_equal(mine, ref), (engine, u)
    if weighted:
        # weights travel with their (src, dst) edge under any stable order
        for u in range(v):
            mine = sorted(zip(np.asarray(csr.targets[off[u]:off[u + 1]]).tolist(),
                              np.round(np.asarray(
                                  csr.weights[off[u]:off[u + 1]]), 3).tolist()))
            ref = sorted(zip(np.asarray(oracle.targets[off[u]:off[u + 1]]).tolist(),
                             np.round(np.asarray(
                                 oracle.weights[off[u]:off[u + 1]]), 3).tolist()))
            assert mine == ref, (engine, u)


@pytest.mark.parametrize("engine", ENGINES)
def test_load_edgelist_infers_num_vertices(tmp_path, engine):
    path, v, e, _ = _graph(tmp_path, weighted=False, base=1, seed=11)
    el = load_edgelist(path, engine=engine, **SMALL_KW.get(engine, {}))
    n = int(el.num_edges)
    assert n == e
    assert el.num_vertices == int(max(np.asarray(el.src[:n]).max(),
                                      np.asarray(el.dst[:n]).max())) + 1


@pytest.mark.parametrize("engine", ["device", "numpy", "threads"])
def test_empty_file(tmp_path, engine):
    path = str(tmp_path / "empty.el")
    open(path, "w").close()
    el = load_edgelist(path, engine=engine)
    assert int(el.num_edges) == 0
    csr = load_csr(path, engine=engine)
    assert csr.num_rows == 0
    assert np.asarray(csr.offsets).tolist() == [0]


def test_load_edgelist_offset_skips_header(tmp_path):
    path = str(tmp_path / "hdr.el")
    header = "9999 9999 9999\n"
    with open(path, "w") as f:
        f.write(header)
        f.write("1 2\n3 4\n")
    el = load_edgelist(path, engine="numpy", offset=len(header))
    n = int(el.num_edges)
    assert n == 2
    assert np.asarray(el.src[:n]).tolist() == [0, 2]


def test_symmetric_through_front_door(tmp_path):
    path, v, e, _ = _graph(tmp_path, weighted=False, base=1, seed=4)
    for engine in ["device", "numpy"]:
        el = load_edgelist(path, engine=engine, symmetric=True,
                           num_vertices=v, **SMALL_KW.get(engine, {}))
        assert int(el.num_edges) == 2 * e


@pytest.mark.slow
def test_streaming_device_csr_large_graph(tmp_path):
    """Acceptance: fused device load_csr == csr_np oracle on >= 1M edges,
    no host EdgeList in between (the fused path in loader.load_csr)."""
    from repro.core import make_graph_file, read_edgelist_numpy

    path = str(tmp_path / "big.el")
    v, e = make_graph_file(path, "rmat", scale=16, edge_factor=16, seed=1)
    assert e >= 1_000_000
    csr = load_csr(path, engine="device", num_vertices=v)
    el = read_edgelist_numpy(path, num_vertices=v)
    n = int(el.num_edges)
    oracle = csr_np(np.asarray(el.src[:n]), np.asarray(el.dst[:n]), None, v)
    assert np.array_equal(np.asarray(csr.offsets, np.int64), oracle.offsets)
    off = oracle.offsets
    rng = np.random.default_rng(0)
    for u in rng.integers(0, v, 200):
        assert np.array_equal(
            np.sort(np.asarray(csr.targets[off[u]:off[u + 1]])),
            np.sort(oracle.targets[off[u]:off[u + 1]])), u
