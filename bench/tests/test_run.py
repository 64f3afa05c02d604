"""CPU rehearsals of each cell at a tiny size, through the whole of a
run but the look for a chip, and the comparison that decides
``correct``: the control and the faults of the timed path fail it."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import compare, readings, run
from bench.profile_reader import Trace
from repro.core.types import CSR

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
RECORDED = Path(__file__).parent / "data" / "tpu_v5e_graph500_s14_text.json"
TINY = {
    "graph500-s20": {"scale": 9, "num_vertices": 512, "num_edges": 8192,
                     "text_bytes": 3 * 262144},
    "road-d-grid": {"side": 48, "num_vertices": 48 * 48,
                    "num_edges": 4 * 48 * 47, "text_bytes": 262144},
}
CELLS = {w["name"]: w["config"] for w in BENCH["workloads"]}
SEED = 2**31 + 77


@pytest.fixture(autouse=True)
def cpu_as_chip(monkeypatch):
    """Skip the look for a chip: the CPU stands in, with v5e peaks."""
    monkeypatch.setattr(run, "look_for_chip", lambda cell: (
        jax.devices()[:cell.chips], {"hbm_bytes_per_s": 819e9}))


def _run(cell, trace=False, seconds=0.5):
    return run.run_cell(cell, SEED, seconds, trace,
                        overrides=TINY[CELLS[cell]])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rehearsal(cell):
    res, lines = _run(cell)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    # the CPU reports no peak bytes
    assert set(res["metrics"]) == {"load_edges_per_s", "setup_s"}
    assert len(lines) == len(res["checks"])
    assert not (run.DATA_DIR / cell).exists()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_rehearsal(monkeypatch, cell):
    """The profiler runs for real; its CPU trace has the harness's and
    the driver's annotations and no device plane, so the readers read
    the trace recorded on a TPU v5e in its place."""
    seen = []

    def read(log_dir):
        with pytest.raises(ValueError, match="no device"):
            real_read(log_dir)
        seen.append(log_dir)
        return Trace.from_json(RECORDED.read_text())

    real_read = run.read_trace
    monkeypatch.setattr(run, "read_trace", read)
    res, _ = _run(cell, trace=True)
    assert seen and res["correct"] and res["attempted"] == 1
    want = {m["name"] for m in BENCH["per_layer"] if cell in m["workloads"]}
    assert set(res["metrics"]) == want
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_fails(cell):
    cfg = TINY[CELLS[cell]]
    limits = run.spec.load_cell(cell).config["limits"]
    for seed in (1, 2, 3):
        r = readings.control_readings(cell, seed, cfg)
        assert any(v > limits[k] for k, v in r.items()), r


def _broken(monkeypatch, alter):
    """Every operation's CSR altered where the load produced it."""
    real = run.spec.plugin

    def plugin(folder, name):
        mod = real(folder, name)
        if folder != "drivers":
            return mod
        prepare = mod.prepare

        def broken_prepare(*a):
            d = prepare(*a)
            op = d.op

            def altered(k, mark):
                c = op(k, mark)
                return alter(CSR(np.array(c.offsets), np.array(c.targets),
                                 None if c.weights is None
                                 else np.array(c.weights), c.num_vertices))
            d.op = altered
            return d
        mod.prepare = broken_prepare
        return mod
    monkeypatch.setattr(run.spec, "plugin", plugin)


def _half(c):
    """Half of the edges left out: each row keeps its first half."""
    deg = np.diff(c.offsets)
    keep = deg // 2
    off = np.concatenate([[0], np.cumsum(keep)])
    idx = np.concatenate([np.arange(a, a + k)
                          for a, k in zip(c.offsets[:-1], keep)])
    return CSR(off, c.targets[idx],
               None if c.weights is None else c.weights[idx], c.num_vertices)


def _one_target(c):
    c.targets[len(c.targets) // 2] ^= 1
    return c


FAULTS = [(cell, f) for cell in sorted(CELLS) for f in (_half, _one_target)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_fails_correct(monkeypatch, cell, fault):
    _broken(monkeypatch, fault)
    res, _ = _run(cell, seconds=0.2)
    assert not res["correct"] and res["failed"] == res["attempted"] >= 1


def test_refuses_a_backend_that_is_not_a_tpu():
    """Run as the driver runs it, on this CPU: no result line, exit != 0."""
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "graph500-s20.gvel", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, capture_output=True,
                       text=True, env={"JAX_PLATFORMS": "cpu",
                                       "PATH": "/usr/bin:/bin"}, timeout=120)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "no TPU" in p.stderr


def test_weights_control_and_fault_fail_the_road_grid_limits():
    """``road-d-grid`` has no cell while the program's staging fault
    stands (PERF.md), but its weight comparison is kept ready: the
    bfloat16 control and one distance off by one break its limits."""
    config = json.loads((ROOT / "bench" / "configs"
                         / "road-d-grid.json").read_text())
    config.update(TINY["road-d-grid"])
    limits = config["limits"]
    for seed in (1, 2, 3):
        g = run.graphs.make(config, seed)
        ref = compare.reference(g)
        ctl = compare.readings_of(compare.control(config["control"], g),
                                  ref, True)
        assert any(v > limits[k] for k, v in ctl.items()), ctl
        w = ref.weights.copy()
        w[len(w) // 3] += np.float32(1)
        off = CSR(ref.offsets, ref.targets, w, ref.num_vertices)
        assert compare.readings_of(off, ref, True)["weights_max_ulp"] > 0
        checks, failed = compare.judge([off], ref, True, limits)
        assert failed == 1


def test_judge_counts_shape_errors():
    g = run.graphs.Graph(np.array([0, 1]), np.array([1, 0]), None, 2)
    ref = compare.reference(g)
    short = CSR(ref.offsets[:-1], ref.targets, None, 2)
    checks, failed = compare.judge([short], ref, False,
                                   {"offsets_mismatch": 0,
                                    "targets_mismatch": 0})
    assert failed == 1 and checks["offsets_mismatch"]["value"] > 0
