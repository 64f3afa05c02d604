#!/usr/bin/env python3
"""The GVEL chip benchmark: one run of one cell.

    python3 bench/run.py --workload graph500-s20.text --seed 7 \
        --seconds 20 --trace 0

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a graph
configuration (``bench/configs/``) under a traffic mix
(``bench/traffic/``).  The configuration names its graph family
(``bench/gen/graphs/``), the traffic its window driver
(``bench/drivers/``) and input format (``bench/inputs/``), and
``BENCHMARK.json`` the metrics, each read by a file of its own
(``bench/e2e/``, ``bench/metrics/``).  A run:

1. set-up: starts JAX with the program's persistent compilation cache,
   refuses a backend that is not a TPU (or has fewer chips than the cell
   asks for, or is missing from ``bench/peaks.json``), makes the graph
   from ``--seed``, lets the driver write its inputs, and makes one
   whole warm-up operation, which compiles (or finds the cache) and
   warms the page cache;
2. the window: the driver's operations back to back; one starts while
   less than ``--seconds`` have passed, so the window overruns by at
   most one operation.  With ``--trace 1`` the window is one operation
   traced with ``jax.profiler``, its phases annotated by the driver;
3. the check: after the window, the driver compares every result the
   window returned with the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its
limit.  The same numbers end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import compare, spec  # noqa: E402
from bench.e2e import Window  # noqa: E402
from bench.gen import graphs  # noqa: E402
from bench.metrics import Context  # noqa: E402
from bench.profile_reader import read_trace  # noqa: E402

DATA_DIR = ROOT / "bench" / ".data"


class NoChip(RuntimeError):
    """The backend is not the chip the cell asks for."""


def look_for_chip(cell: spec.Cell):
    """``(devices, peaks)``: the cell's chips and their row of
    ``bench/peaks.json``; raises :class:`NoChip` where they are not."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (device 0 is {devs[0].platform!r}); "
                     f"the benchmark does not fall back to another backend")
    if len(devs) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chip(s), JAX has "
                     f"{len(devs)}")
    with open(ROOT / "bench" / "peaks.json") as f:
        table = json.load(f)
    kind = devs[0].device_kind
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return devs[:cell.chips], table[kind]


def _no_mark(_name):
    return contextlib.nullcontext()


def _traced_op(driver, k: int, log_dir: str):
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.op"):
            return driver.op(k, jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             overrides: dict | None = None) -> tuple[dict, list[str]]:
    """One run; returns the result's line (as a dict) and the lines of
    the numbers compared.  ``overrides`` serves the tests' tiny
    rehearsals on the CPU; a benchmark run takes none."""
    cell = spec.load_cell(name, overrides)
    import jax

    from repro.core import env

    env.use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs, peaks = look_for_chip(cell)
    graph = graphs.make(cell.config, seed)
    workdir = str(DATA_DIR / cell.name)
    metrics, device_extra, breakdown, results = {}, {}, None, []
    try:
        driver = spec.plugin("drivers", cell.traffic["driver"]).prepare(
            cell, graph, workdir)
        driver.op(0, _no_mark)      # compile (or hit the cache), page cache
        if trace:
            log_dir = os.path.join(workdir, "trace")
            results.append(_traced_op(driver, 1, log_dir))
            tr = read_trace(log_dir)
            ctx = Context(tr, graph.num_vertices, graph.num_edges,
                          cell.weighted, driver.input_bytes, peaks)
            for m in cell.per_layer:
                value = spec.plugin("metrics", m["name"]).read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device_extra = {"busy_s": tr.busy_ns() / 1e9,
                            "window_s": tr.window_ns / 1e9}
            breakdown = tr.breakdown()
        else:
            t0 = time.perf_counter()
            ops, k = [], 1
            while time.perf_counter() - t0 < seconds:
                s = time.perf_counter()
                results.append(driver.op(k, _no_mark))
                ops.append((s, time.perf_counter(), driver.units))
                k += 1
            w = Window(t0 - T_START, t0, ops, devs)
            for m in cell.end_to_end:
                value = spec.plugin("e2e", m["name"]).read(w)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        in_use = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                  for d in devs]
        peak = max((b for b in in_use if b is not None), default=None)
        checks, failed = driver.check(results)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    result = {
        "correct": compare.correct(checks, failed, len(results)),
        "attempted": len(results),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs), "memory_peak_bytes": peak,
                   **device_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result, compare.lines(checks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, check_lines = run_cell(args.workload, args.seed, args.seconds,
                                       bool(args.trace))
    except NoChip as exc:
        print(f"bench/run.py: {exc}", file=sys.stderr, flush=True)
        return 2
    for line in check_lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
