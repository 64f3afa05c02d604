"""The load path's spans (``repro.core.trace``), recorded for real by a
``jax.profiler`` session on the CPU: each span of a small load appears
the right number of times, on the host, under its ``load.*`` name."""
import collections
import functools
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import loader, open_graph
from repro.core.generate import write_edgelist

BETA, BATCH_BLOCKS = 1 << 14, 4
B = "batches"         # stands for the text load's number of batches
SPANS = ("load.open", "load.accumulators", "load.stage", "load.stage_wait",
         "load.put", "load.snapshot_read", "load.sync", "load.build_dispatch",
         "load.copy_back")
# span -> times it opens in one open_graph(path).csr() of each kind of
# load; None is "at least once" (the snapshot engine memoises its open)
EXPECTED = {
    "text": {"load.open": 1, "load.accumulators": 1, "load.stage": B,
             "load.stage_wait": B, "load.put": B,
             "load.snapshot_read": 0, "load.sync": 2,
             "load.build_dispatch": 1, "load.copy_back": 1},
    # staging inline, as each shard of the sharded loader does
    "text-inline": {"load.open": 1, "load.accumulators": 1,
                    "load.stage": B, "load.stage_wait": 0,
                    "load.put": B, "load.snapshot_read": 0,
                    "load.sync": 2, "load.build_dispatch": 1,
                    "load.copy_back": 1},
    "gvel": {"load.open": 1, "load.accumulators": 0, "load.stage": 0,
             "load.stage_wait": 0, "load.put": 2, "load.snapshot_read": None,
             "load.sync": 2, "load.build_dispatch": 1, "load.copy_back": 1},
}


def _traced_span_counts(log_dir, load):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        csr = load()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    counts = collections.Counter()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("load."):
                    assert plane.name.startswith("/host:"), plane.name
                    counts[ev.name] += 1
    return counts, csr


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = tmp_path_factory.mktemp("spans")
    rng = np.random.default_rng(3)
    v = 300
    src = rng.integers(0, v, 20000).astype(np.int32)
    dst = rng.integers(0, v, 20000).astype(np.int32)
    text = str(d / "g.el")
    write_edgelist(text, src, dst, base=1)
    gvel = str(d / "g.gvel")
    open_graph(text, num_vertices=v).save(gvel, csr=False)
    blocks = -(-os.path.getsize(text) // BETA)
    kw = dict(num_vertices=v, beta=BETA, batch_blocks=BATCH_BLOCKS)
    load_text = lambda: open_graph(text, **kw).csr()      # noqa: E731
    load_gvel = lambda: open_graph(gvel).csr()            # noqa: E731
    ref = load_text()                                     # compile first
    load_gvel()
    out = {"text": _traced_span_counts(d / "t", load_text),
           "gvel": _traced_span_counts(d / "g", load_gvel)}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(loader, "_parse_span", functools.partial(
            loader._parse_span, prefetch=False))
        out["text-inline"] = _traced_span_counts(d / "i", load_text)
    return out, ref, -(-blocks // BATCH_BLOCKS)


@pytest.mark.parametrize("span", SPANS)
@pytest.mark.parametrize("kind", sorted(EXPECTED))
def test_span_counts(traced, kind, span):
    counts, _csr = traced[0][kind]
    want = EXPECTED[kind][span]
    if want == B:
        want = traced[2]
        assert want >= 3
    if want is None:
        assert counts[span] >= 1
    else:
        assert counts[span] == want


@pytest.mark.parametrize("kind", sorted(EXPECTED))
def test_traced_loads_are_right(traced, kind):
    (counts, csr), ref = traced[0][kind], traced[1]
    assert set(counts) <= set(SPANS)
    assert np.array_equal(csr.offsets, ref.offsets)
    assert np.array_equal(csr.targets, ref.targets)
