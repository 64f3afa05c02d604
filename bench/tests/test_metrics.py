"""Each per-layer reader on a trace recorded on a TPU v5e (one traced
load of a scale-14 Graph500 text through ``open_graph(...).csr()``),
and the case where the events it reads are missing."""
import dataclasses
import json
from pathlib import Path

import pytest

from bench.metrics import Context
from bench.profile_reader import Trace
from bench.spec import plugin

DATA = Path(__file__).parent / "data" / "tpu_v5e_graph500_s14_text.json"
BENCH = json.loads((Path(__file__).resolve().parents[2]
                    / "BENCHMARK.json").read_text())
PEAKS = {"hbm_bytes_per_s": 819e9}
V, E, FILE_BYTES = 2**14, 16 * 2**14, 3_560_000


@pytest.fixture()
def ctx():
    return Context(Trace.from_json(DATA.read_text()), V, E, False,
                   FILE_BYTES, PEAKS)


def reader(name):
    return plugin("metrics", name).read


def _sum(trace, name):
    return sum(e - s for _d, n, s, e in trace.modules if n == name)


def test_every_per_layer_metric_has_a_reader():
    for m in BENCH["per_layer"]:
        assert callable(reader(m["name"]))


def test_device_idle_share(ctx):
    got = reader("device_idle_share")(ctx)
    assert 0 < got < 100
    assert got == pytest.approx(100 * (1 - ctx.trace.busy_ns()
                                       / ctx.trace.window_ns))


def test_busy_is_a_union_not_a_sum():
    t = Trace((0.0, 10.0), [], [(0, "a", 1, 4), (0, "b", 2, 6),
                                (0, "c", 8, 12)], [], 1)
    assert t.busy_ns() == 7.0            # [1, 6) and [8, 10)


@pytest.mark.parametrize("name,module", [
    ("parse_device_share", "jit__parse_accumulate_impl"),
    ("build_device_share", "jit_csr_staged")])
def test_device_shares(ctx, name, module):
    got = reader(name)(ctx)
    assert got == pytest.approx(100 * _sum(ctx.trace, module)
                                / ctx.trace.window_ns)
    assert 0 < got < 100


def test_parse_roofline(ctx):
    t = _sum(ctx.trace, "jit__parse_accumulate_impl") / 1e9
    want = 100 * (FILE_BYTES + 8 * E) / 819e9 / t
    assert reader("parse_roofline")(ctx) == pytest.approx(want)
    weighted = dataclasses.replace(ctx, weighted=True)
    assert reader("parse_roofline")(weighted) == pytest.approx(
        100 * (FILE_BYTES + 12 * E) / 819e9 / t)


def test_build_roofline(ctx):
    t = _sum(ctx.trace, "jit_csr_staged") / 1e9
    want = 100 * 4 * (2 * E + E + V + 1) / 819e9 / t
    assert reader("build_roofline")(ctx) == pytest.approx(want)
    assert 0 < want < 100


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_missing_events_read_none(ctx, name):
    """Programs renamed (or gone) and no device operations: nothing to
    read, and the reader says so rather than 0."""
    t = ctx.trace
    renamed = dataclasses.replace(
        t, ops=[], modules=[(d, "jit_renamed", s, e)
                            for d, _n, s, e in t.modules])
    assert reader(name)(dataclasses.replace(ctx, trace=renamed)) is None


def test_breakdown_names_ops_and_gaps(ctx):
    b = ctx.trace.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("jit__parse_accumulate_impl:")
    assert all(g[0].split("+")[0] in ("bench.open", "bench.csr",
                                      "bench.ready", "outside")
               for g in b["idle_gaps"])
    gaps = [g[1] for g in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
