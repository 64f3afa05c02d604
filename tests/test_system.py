"""End-to-end behaviour tests: file -> GVEL -> CSR -> walks -> training."""
import os

import numpy as np
import jax
import pytest

from repro.configs import reduced_config
from repro.core import convert_to_csr, make_graph_file, read_csr, read_edgelist
from repro.data.pipeline import Prefetcher
from repro.data.walks import walk_batch
from repro.models import init_params
from repro.train.optimizer import OptimizerConfig
from repro.train.state import init_state
from repro.train.step import make_train_step


@pytest.fixture(scope="module")
def graph(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("sys") / "g.el")
    v, e = make_graph_file(path, "rmat", scale=9, edge_factor=8, seed=21)
    return path, v, e


def test_end_to_end_graph_to_training(graph):
    """The paper's technique as the data substrate: text file -> staged CSR
    -> random-walk corpus -> LM training; loss must drop."""
    path, v, e = graph
    csr = read_csr(path, num_vertices=v)
    assert int(csr.offsets[-1]) == e

    cfg = reduced_config("phi4-mini-3.8b")
    params = init_params(jax.random.key(0), cfg)
    state = init_state(params)
    oc = OptimizerConfig(lr=2e-3, warmup_steps=2, decay_steps=60)
    step = jax.jit(make_train_step(cfg, oc))

    losses = []
    for i in range(30):
        batch = walk_batch(csr, cfg, 8, 32, step=i)
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.9, losses


def test_end_to_end_with_prefetcher(graph):
    path, v, e = graph
    csr = read_csr(path, num_vertices=v, engine="numpy")
    cfg = reduced_config("phi4-mini-3.8b")
    state = init_state(init_params(jax.random.key(1), cfg))
    oc = OptimizerConfig(lr=1e-3, warmup_steps=2, decay_steps=60)
    step = jax.jit(make_train_step(cfg, oc))
    pf = Prefetcher(lambda i: walk_batch(csr, cfg, 4, 16, i), lookahead=2)
    try:
        for i in range(5):
            state, m = step(state, pf.get(expect_step=i))
            assert np.isfinite(float(m["loss"]))
    finally:
        pf.close()
    assert int(state.step) == 5


def test_jax_engine_matches_numpy_engine_on_csr(graph):
    path, v, e = graph
    a = read_csr(path, num_vertices=v, engine="jax")
    b = read_csr(path, num_vertices=v, engine="numpy")
    assert np.array_equal(np.asarray(a.offsets, np.int64),
                          np.asarray(b.offsets))


def test_train_driver_cli(tmp_path, graph):
    from repro.launch.train import main
    path, v, e = graph
    rc = main(["--arch", "musicgen-large", "--reduced", "--steps", "3",
               "--batch", "2", "--seq", "16",
               "--ckpt-dir", str(tmp_path / "ck")])
    assert rc == 0


def test_serve_driver_cli():
    from repro.launch.serve import main
    rc = main(["--arch", "phi4-mini-3.8b", "--reduced", "--requests", "3",
               "--max-new", "4", "--batch", "2", "--max-seq", "32"])
    assert rc == 0
