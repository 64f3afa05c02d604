"""The four-chip cell ``graph500-s22.text-mesh4``: its driver's host
assembly of the sharded CSR against the plain reference, its five
readers on synthetic four-device traces (and on a real profiler
session's counters), and a rehearsal of whole runs on four virtual CPU
devices."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import compare, readings
from bench.gen import graphs
from bench.metrics import Context
from bench.metrics import spans as span_mod
from bench.metrics.spans import SpanTrace
from bench.profile_reader import Trace
from bench.spec import load_cell, plugin

ROOT = Path(__file__).resolve().parents[2]
CELL = "graph500-s22.text-mesh4"
NEW = ("exchange_device_share", "exchange_roofline", "all_to_all_share",
       "mesh_idle_share", "exchange_fill")
TINY = {"scale": 12, "num_vertices": 4096, "num_edges": 65536,
        "text_bytes": 4 * 262144}
PEAKS = {"hbm_bytes_per_s": 819e9}
OLD = Path(__file__).parent / "data" / "tpu_v5e_graph500_s14_text.json"

driver = plugin("drivers", "load_csr_sharded")


def reader(name):
    return plugin("metrics", name).read


def _split(ref, d):
    """The reference cut as docs/distributed.md lays a sharded CSR out:
    shard k has rows [k*rows, (k+1)*rows), offsets from 0, padding rows
    past V empty."""
    v = ref.num_vertices
    rows = -(-v // d)
    padded = np.concatenate([ref.offsets,
                             np.full(rows * d - v, ref.offsets[-1])])
    offs, tgts = [], []
    for k in range(d):
        o = padded[k * rows:(k + 1) * rows + 1]
        offs.append((o - o[0]).astype(np.int32))
        tgts.append(ref.targets[o[0]:o[-1]])
    return driver.HostShards(offs, tgts, v)


@pytest.mark.parametrize("v,d", [(512, 4), (13, 4), (100, 3), (7, 1)])
def test_assembly_equals_the_reference(v, d):
    rng = np.random.default_rng(v * 10 + d)
    n = 40 * v
    g = graphs.Graph(rng.integers(0, v, n), rng.integers(0, v, n), None, v)
    ref = compare.reference(g)
    shards = _split(ref, d)
    assert [len(o) for o in shards.offsets] == [-(-v // d) + 1] * d
    got = driver.assemble(shards)
    assert np.array_equal(got.offsets, ref.offsets)
    assert np.array_equal(got.targets, ref.targets)
    checks, failed = compare.judge([got], ref, False,
                                   {"offsets_mismatch": 0,
                                    "targets_mismatch": 0})
    assert failed == 0 and all(c["value"] == 0 for c in checks.values())


def _off_by_one_row(s):
    """Shard 1 counted one row too early: its first row's edges moved to
    shard 0's last row."""
    o = [x.copy() for x in s.offsets]
    first = int(o[1][1])
    o[0][-1] += first
    o[1] = o[1] - first
    o[1][0] = 0
    t = [np.concatenate([s.targets[0], s.targets[1][:first]]),
         s.targets[1][first:], *s.targets[2:]]
    return s._replace(offsets=o, targets=t)


def _shards_swapped(s):
    return s._replace(offsets=s.offsets[::-1], targets=s.targets[::-1])


def _prefix_short(s):
    return s._replace(targets=[t[:-1] for t in s.targets])


@pytest.mark.parametrize("fault", [_off_by_one_row, _shards_swapped,
                                   _prefix_short])
def test_assembly_of_a_wrong_layout_fails(fault):
    rng = np.random.default_rng(5)
    v, n = 400, 9000
    g = graphs.Graph(rng.integers(0, v, n), rng.integers(0, v, n), None, v)
    ref = compare.reference(g)
    got = driver.assemble(fault(_split(ref, 4)))
    checks, failed = compare.judge([got], ref, False,
                                   {"offsets_mismatch": 0,
                                    "targets_mismatch": 0})
    assert failed == 1


def _ctx(trace, v=2**12, e=2**16):
    return Context(trace, v, e, False, 0, PEAKS)


def _four(spans=()):
    """A 100 ns window on four devices.  Device k runs the exchange
    program over [40, 40 + 10(k+1)) with an all-to-all of 2 ns inside
    it, and a parse op over [0, 20 + 5k)."""
    ops, mods = [], []
    for k in range(4):
        ops += [(k, "fusion.1", 0.0, 20.0 + 5 * k),
                (k, "all_to_all.15", 41.0, 43.0),
                (k, "all_to_all.17", 42.0, 44.0),
                (k, "fusion.9", 44.0, 40.0 + 10 * (k + 1))]
        mods += [(k, "jit__parse_accumulate_impl", 0.0, 20.0 + 5 * k),
                 (k, "jit_exchange_build", 40.0, 40.0 + 10 * (k + 1))]
    return SpanTrace((0.0, 100.0), [], ops, mods, 4, list(spans))


def test_exchange_device_share_averages_the_chips():
    assert reader("exchange_device_share")(_ctx(_four())) == \
        pytest.approx(25.0)                       # (10+20+30+40)/4 of 100


def test_exchange_roofline_counts_a_chips_bytes():
    v, e = 2**12, 2**16
    need = 4 * (7 * e / 4 + v / 4 + 1)
    want = 100 * need / 819e9 / 25e-9
    assert reader("exchange_roofline")(_ctx(_four(), v, e)) == \
        pytest.approx(want)


def test_all_to_all_share_is_a_union_per_chip():
    assert reader("all_to_all_share")(_ctx(_four())) == pytest.approx(3.0)


def test_mesh_idle_share_is_per_chip():
    # a join over [0, 40): device k idles [20 + 5k, 40); a histogram
    # over [38, 45) counts [38, 40) once more (no) and adds [40, 41),
    # before the all-to-all; a stage span is no sync
    spans = [("load.shard_join", 0.0, 40.0), ("load.bucket_histogram",
                                              38.0, 45.0),
             ("load.stage", 60.0, 70.0)]
    idle = sum(40 - (20 + 5 * k) for k in range(4)) / 4 + 1
    assert reader("mesh_idle_share")(_ctx(_four(spans))) == \
        pytest.approx(idle)
    assert reader("mesh_idle_share")(_ctx(_four())) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_on_a_one_chip_load(name, monkeypatch,
                                                 tmp_path):
    """The recorded one-chip text load has no mesh program, collective,
    span or counter: every reader leaves its metric out."""
    monkeypatch.setattr(span_mod, "LOGS", tmp_path)
    monkeypatch.setattr(span_mod, "_found", {})
    monkeypatch.setattr(plugin("metrics", "exchange_fill"), "LOGS",
                        tmp_path)
    t = Trace.from_json(OLD.read_text())
    assert reader(name)(_ctx(t)) is None


def test_exchange_fill_reads_the_spans_counters(tmp_path):
    """A real profiler session on this CPU: the counters of
    ``load.exchange`` inside the window, found on disk."""
    import jax

    from repro.core import trace

    log_dir = tmp_path / "cell" / "trace"
    jax.profiler.start_trace(str(log_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.op"):
            with trace.span("load.exchange", shards=4, send_cap=48,
                            edge_limit=200, edges=600):
                pass
    finally:
        jax.profiler.stop_trace()
    fill = plugin("metrics", "exchange_fill")
    (f,) = log_dir.rglob("*.xplane.pb")
    window = None
    for plane in jax.profiler.ProfileData.from_file(str(f)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench.op":
                    window = (float(ev.start_ns),
                              float(ev.start_ns) + float(ev.duration_ns))
    assert fill.find_stats(window, fill.SPAN, tmp_path) == [
        {"shards": 4, "send_cap": 48, "edge_limit": 200, "edges": 600}]
    assert fill.find_stats((0.0, 1.0), fill.SPAN, tmp_path) == []
    fill_mod_logs = fill.LOGS
    try:
        fill.LOGS = tmp_path
        got = fill.read(_ctx(Trace(window, [], [], [], 1)))
    finally:
        fill.LOGS = fill_mod_logs
    assert got == pytest.approx(100 * 600 / (16 * 48))


REHEARSAL = r"""
import json, sys
import jax
from bench import run
from bench.profile_reader import Trace
from bench.spec import plugin

run.look_for_chip = lambda cell: (jax.devices()[:cell.chips],
                                  {"hbm_bytes_per_s": 819e9})
tiny = json.loads(sys.argv[1])
res, _ = run.run_cell("%(cell)s", 2**31 + 5, 0.5, False, overrides=tiny)
print("UNTRACED", json.dumps(res))

def four_device_trace(log_dir):
    # the CPU trace has no device plane: the window and the spans are
    # the real ones, four devices' programs are laid in its second half
    import glob
    f = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    for plane in jax.profiler.ProfileData.from_file(f).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench.op":
                    lo = float(ev.start_ns)
                    hi = lo + float(ev.duration_ns)
    mid = (lo + hi) / 2
    ops = [(k, "all_to_all.3", mid, mid + 1.0) for k in range(4)]
    mods = [(k, "jit_exchange_build", mid, hi) for k in range(4)]
    return Trace((lo, hi), [], ops, mods, 4)

run.read_trace = four_device_trace
res, _ = run.run_cell("%(cell)s", 2**31 + 6, 0.5, True, overrides=tiny)
print("TRACED", json.dumps(res))

# the timed path at fault: one target of the third chip altered
drv = plugin("drivers", "load_csr_sharded")
real_to_host = drv.to_host
def altered(csr):
    h = real_to_host(csr)
    t = h.targets[2].copy()
    t[len(t) // 2] ^= 1
    h.targets[2] = t
    return h
drv.to_host = altered
run.spec.plugin = lambda folder, name: (drv if folder == "drivers"
                                        else plugin(folder, name))
res, _ = run.run_cell("%(cell)s", 2**31 + 7, 0.2, False, overrides=tiny)
print("ALTERED", json.dumps(res))
""" % {"cell": CELL}


def test_rehearsal_on_four_cpu_devices():
    """Whole runs of the cell at a tiny size on four virtual devices:
    every load correct; traced, every new reader reads (the device
    programs are synthetic, the spans and counters real)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT / 'src'}{os.pathsep}{ROOT}")
    p = subprocess.run([sys.executable, "-c", REHEARSAL, json.dumps(TINY)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = {line.split(" ", 1)[0]: json.loads(line.split(" ", 1)[1])
           for line in p.stdout.splitlines()
           if line.startswith(("UNTRACED ", "TRACED ", "ALTERED "))}
    plain, traced, altered = out["UNTRACED"], out["TRACED"], out["ALTERED"]
    assert not altered["correct"]
    assert altered["failed"] == altered["attempted"] >= 1
    assert altered["checks"]["targets_mismatch"]["value"] >= 1
    assert plain["correct"] and plain["attempted"] >= 1
    assert plain["device"]["count"] == 4
    assert set(plain["metrics"]) == {"load_edges_per_s", "setup_s"}
    assert traced["correct"] and traced["attempted"] == 1
    cell = load_cell(CELL)
    assert {m["name"] for m in cell.per_layer} == set(NEW)
    assert set(traced["metrics"]) == set(NEW)
    fill = traced["metrics"]["exchange_fill"]["value"]
    assert 0 < fill <= 100
    assert 0 < traced["metrics"]["exchange_roofline"]["value"] <= 100
    assert traced["metrics"]["mesh_idle_share"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_control_fails(seed):
    """The unstable sort in the program's place breaks the limits."""
    limits = load_cell(CELL).config["limits"]
    r = readings.control_readings(CELL, seed, TINY)
    assert any(v > limits[k] for k, v in r.items()), r


def test_the_cell_is_the_full_scale_graph():
    """No cut of scale: the published graph500-22, and a padding that
    gives each of the four chips the same whole number of blocks."""
    cell = load_cell(CELL)
    c = cell.config
    assert (c["scale"], c["edge_factor"]) == (22, 16)
    assert (c["num_vertices"], c["num_edges"]) == (2**22, 16 * 2**22)
    assert cell.chips == cell.traffic["mesh_width"] == 4
    assert c["text_bytes"] % (4 * 262144) == 0
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cfg,) = [x for x in bench["configs"] if x["name"] == c["name"]]
    assert cfg["reduced"] == []


def test_spans_survive_as_data():
    t = _four([("load.shard_join", 0.0, 40.0)])
    back = SpanTrace.from_json(json.dumps(dataclasses.asdict(t)))
    assert reader("mesh_idle_share")(_ctx(back)) == \
        reader("mesh_idle_share")(_ctx(t))
