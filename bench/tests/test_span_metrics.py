"""The readers of the program's own spans (``load.*``): on synthetic
traces, where the answer is known, and on a traced load recorded on a
TPU v5e with the spans in it (one scale-14 Graph500 text load through
``open_graph(...).csr()``, as ``bench/run.py --trace 1`` traces it).
The readers that were there before the spans read the same numbers, and
the spans are found on disk in a real profiler session's ``.xplane.pb``,
as ``bench/run.py --trace 1`` leaves it."""
import dataclasses
import json
from pathlib import Path

import pytest

from bench.metrics import Context
from bench.metrics import spans as span_mod
from bench.metrics.spans import SpanTrace, find_spans, idle_by_span
from bench.profile_reader import Trace
from bench.spec import plugin

DATA = Path(__file__).parent / "data"
OLD = DATA / "tpu_v5e_graph500_s14_text.json"
SPANS = DATA / "tpu_v5e_graph500_s14_text_spans.json"
PEAKS = {"hbm_bytes_per_s": 819e9}
V, E = 2**14, 16 * 2**14
OLD_BYTES, SPANS_BYTES = 3_560_000, 2_789_790
SPAN_READERS = {"staging_share": "load.stage", "transfer_share": "load.put",
                "accumulators_share": "load.accumulators",
                "copy_back_share": "load.copy_back"}
NEW = (*SPAN_READERS, "unexplained_idle_share")
# each reader's value on the older recording, read before the span
# readers were added
BEFORE = {"device_idle_share": 29.7152226925007,
          "parse_device_share": 60.85144509847872,
          "parse_roofline": 0.003941425659999075,
          "build_device_share": 9.430751984184566,
          "build_roofline": 0.014436323853360248}


def reader(name):
    return plugin("metrics", name).read


def _ctx(trace, file_bytes=OLD_BYTES):
    return Context(trace, V, E, False, file_bytes, PEAKS)


def _synthetic(spans):
    """Device busy [0, 2) and [5, 6) of a 10 ns window."""
    return _ctx(SpanTrace((0.0, 10.0), [], [(0, "a", 0, 2), (0, "b", 5, 6)],
                          [], 1, spans))


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_readers_before_the_spans_read_as_before(name):
    assert reader(name)(_ctx(Trace.from_json(OLD.read_text()))) == \
        BEFORE[name]


@pytest.mark.parametrize("kind", [Trace, SpanTrace])
def test_old_recording_has_no_spans(kind, monkeypatch, tmp_path):
    monkeypatch.setattr(span_mod, "LOGS", tmp_path)
    monkeypatch.setattr(span_mod, "_found", {})
    t = kind.from_json(OLD.read_text())
    assert span_mod.spans(t) == []
    for name in NEW:
        assert reader(name)(_ctx(t)) is None


def test_span_share_is_a_union_clipped_to_the_window():
    ctx = _synthetic([("load.put", 1, 4), ("load.put", 2, 6),
                      ("load.put", 8, 12), ("load.stage", -5, -1)])
    assert reader("transfer_share")(ctx) == pytest.approx(70.0)
    assert reader("staging_share")(ctx) is None       # only outside
    assert reader("copy_back_share")(ctx) is None     # none at all


def test_idle_by_span_takes_the_innermost_span():
    # idle [2, 5) and [6, 10); load.put nested inside load.sync
    spans = [("load.sync", 1, 4.5), ("load.put", 3, 4),
             ("load.copy_back", 7, 9)]
    t = _synthetic(spans).trace
    assert idle_by_span(t) == pytest.approx(
        {"load.sync": 1.5, "load.put": 1.0, "load.copy_back": 2.0,
         None: 2.5})
    assert reader("unexplained_idle_share")(_synthetic(spans)) == \
        pytest.approx(25.0)


def test_idle_under_spans_of_two_threads_counts_once():
    # a put and a stage of another thread cover the same gap [2, 5)
    spans = [("load.put", 2, 5), ("load.stage", 1.5, 5.5)]
    by = idle_by_span(_synthetic(spans).trace)
    assert by == pytest.approx({"load.put": 3.0, None: 4.0})


def test_unexplained_idle_needs_spans_and_ops():
    assert reader("unexplained_idle_share")(_synthetic([])) is None
    ctx = _synthetic([("load.put", 2, 5)])
    no_ops = dataclasses.replace(ctx, trace=dataclasses.replace(
        ctx.trace, ops=[]))
    assert reader("unexplained_idle_share")(no_ops) is None


def test_spans_survive_json():
    t = _synthetic([("load.put", 1, 4)]).trace
    back = SpanTrace.from_json(json.dumps(dataclasses.asdict(t)))
    assert back.spans == [("load.put", 1, 4)]


@pytest.fixture()
def recorded():
    return _ctx(SpanTrace.from_json(SPANS.read_text()), SPANS_BYTES)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_on_the_recorded_load(recorded, name):
    got = reader(name)(recorded)
    assert got is not None and 0 <= got < 100


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_shares_on_the_recorded_load(recorded, name):
    t = recorded.trace
    got = reader(name)(recorded)
    spans = [(s, e) for n, s, e in t.spans if n == SPAN_READERS[name]]
    assert spans
    # a union never exceeds the sum of its parts, nor the window
    assert got <= 100 * sum(e - s for s, e in spans) / t.window_ns + 1e-9
    assert got > 0


def test_recorded_idle_is_all_attributed(recorded):
    t = recorded.trace
    by = idle_by_span(t)
    idle = reader("device_idle_share")(recorded)
    assert 100 * sum(by.values()) / t.window_ns == pytest.approx(idle)
    assert {k for k in by if k is not None} <= {n for n, _s, _e in t.spans}
    assert reader("unexplained_idle_share")(recorded) <= idle


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_spans_leave_the_other_readers_alone(recorded, name):
    bare = dataclasses.replace(recorded, trace=dataclasses.replace(
        recorded.trace, spans=[]))
    assert reader(name)(recorded) == reader(name)(bare)


def _window_of(log_dir):
    """The ``bench.op`` window of the one ``.xplane.pb`` under
    ``log_dir``, as ``bench.profile_reader.read_trace`` reads it."""
    import jax

    (f,) = Path(log_dir).rglob("*.xplane.pb")
    for plane in jax.profiler.ProfileData.from_file(str(f)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench.op":
                    s = float(ev.start_ns)
                    return s, s + float(ev.duration_ns)
    raise AssertionError("no bench.op")


def test_spans_are_found_on_disk(monkeypatch, tmp_path):
    """A plain Trace, as ``bench/run.py`` reads it, finds its spans in
    the ``.xplane.pb`` of its own window, and only there."""
    import jax
    import jax.numpy as jnp

    log_dir = tmp_path / "cell" / "trace"
    jax.profiler.start_trace(str(log_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.op"):
            with jax.profiler.TraceAnnotation("load.put"):
                jnp.arange(8).block_until_ready()
            with jax.profiler.TraceAnnotation("load.copy_back"):
                pass
    finally:
        jax.profiler.stop_trace()
    lo, hi = _window_of(log_dir)
    got = find_spans((lo, hi), tmp_path)
    assert [n for n, _s, _e in got] == ["load.put", "load.copy_back"]
    assert all(lo <= s <= e <= hi for _n, s, e in got)
    assert find_spans((lo, hi + 1), tmp_path) == []

    monkeypatch.setattr(span_mod, "LOGS", tmp_path)
    monkeypatch.setattr(span_mod, "_found", {})
    ctx = _ctx(Trace((lo, hi), [], [(0, "a", lo, lo + 1)], [], 1))
    assert reader("transfer_share")(ctx) > 0
    assert reader("copy_back_share")(ctx) >= 0
    assert reader("staging_share")(ctx) is None
    assert 0 <= reader("unexplained_idle_share")(ctx) < 100
