#!/usr/bin/env bash
# Tier-1 verify: the exact command from ROADMAP.md, run from any cwd,
# plus the docs link check and a convert.py snapshot round-trip smoke.
#   scripts/verify.sh                 # full tier-1 + smoke
#   scripts/verify.sh -m 'not slow'   # quick loop (skips the 1M-edge test)
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

# global watchdog on the tier-1 lane: a hung test (stuck reader, wedged
# prefetch thread) fails the run instead of wedging it.  SIGTERM first,
# SIGKILL 30s later if pytest won't die.
timeout --kill-after=30 "${VERIFY_TIMEOUT_S:-2400}" \
    python -m pytest -x -q "$@"

# multi-device lane: the sharded streaming tests under 4 forced CPU host
# devices.  (tests/conftest.py pops XLA_FLAGS at import — the device
# oracle tests run in subprocesses that set their own flag — so this
# lane's env only pins the host-side tests' view of the platform.)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    python -m pytest -q tests/test_sharded_stream.py

# docs: every relative link in README.md / docs/*.md must resolve
python scripts/check_links.py README.md docs

# snapshot smoke: tiny text fixture -> scripts/convert.py -> load_csr
# must match the csr_np host oracle, raw and zlib-compressed (.gvel v2)
python - <<'PY'
import os, subprocess, sys, tempfile
import numpy as np
from repro.core import load_csr, make_graph_file, read_edgelist_numpy, read_snapshot
from repro.core.build import csr_np

tmp = tempfile.mkdtemp(prefix="gvel_smoke_")
el_path = os.path.join(tmp, "tiny.el")
v, e = make_graph_file(el_path, "uniform", scale=8, edge_factor=4, seed=3)
el = read_edgelist_numpy(el_path, num_vertices=v)
n = int(el.num_edges)
ref = csr_np(np.asarray(el.src[:n]), np.asarray(el.dst[:n]), None, v)

def check(gv):
    got = load_csr(gv, engine="snapshot")
    assert np.array_equal(np.asarray(got.offsets, np.int64), ref.offsets), gv
    off = ref.offsets
    for u in range(v):
        assert np.array_equal(np.sort(np.asarray(got.targets[off[u]:off[u+1]])),
                              np.sort(ref.targets[off[u]:off[u+1]])), (gv, u)

gv = os.path.join(tmp, "tiny.gvel")
subprocess.run([sys.executable, "scripts/convert.py", el_path, gv,
                "--num-vertices", str(v)], check=True)
check(gv)
gvz = os.path.join(tmp, "tiny.z.gvel")
subprocess.run([sys.executable, "scripts/convert.py", el_path, gvz,
                "--num-vertices", str(v), "--compress", "zlib"], check=True)
assert read_snapshot(gvz).version == 2
check(gvz)
print("snapshot smoke: convert.py round-trip OK (raw + zlib .gvel v2)")
PY

# benchmark smoke: the e2e loader benchmark (incl. compressed + lazy
# rows) must still execute end to end — benchmark code can't rot
# unexecuted.  --json emits machine-readable {name, seconds, mb,
# speedup} rows; BENCH_e2e.json committed from a full (non-quick) run
# is the cross-PR perf trajectory.
python -m benchmarks.e2e_load_csr --quick --json /tmp/BENCH_e2e_quick.json
python - <<'PY'
import json
rows = json.load(open("/tmp/BENCH_e2e_quick.json"))
assert rows and all(set(r) == {"name", "seconds", "mb", "speedup"}
                    for r in rows), rows
print(f"benchmark json: {len(rows)} rows OK")
PY

# perf gate: the streaming engine must never fall back below the batch
# round-trip (speedup >= 1.0 even on the --quick graph, where fixed
# costs compress ratios), and the sharded streaming load at d=4 must
# stay on the same baseline axis (its speedup row is normalized through
# the same-split streaming re-timing; a retrace-per-load regression
# shows up here at ~0.14x).  Floors only — quick-run speedups are not
# comparable to the committed full-run rows, so tolerance mode is for
# full-vs-full diffs across PRs (see scripts/bench_diff.py).
python scripts/bench_diff.py BENCH_e2e.json /tmp/BENCH_e2e_quick.json \
    --require-only --require 'e2e.load_csr_streaming>=1.0' \
    --require 'e2e.load_csr_sharded_d4>=1.0'

# query-service smoke + gate: thousands of mixed point/range/full
# requests through the hot-graph cache (tests/test_query.py and
# tests/test_cache.py run in the main pytest lane above).  The floor
# pins serving a request to never cost more than the naive
# open-full-load-slice answer (speedup >= 1.0) — if the selective
# path rots back to full-section reads, it shows up here.
python -m benchmarks.query_service --quick --json /tmp/BENCH_query_quick.json
python - <<'PY'
import json
rows = json.load(open("/tmp/BENCH_query_quick.json"))
assert rows and all(set(r) == {"name", "seconds", "mb", "speedup"}
                    for r in rows), rows
names = {r["name"] for r in rows}
assert "e2e.query_mixed" in names, names
print(f"query benchmark json: {len(rows)} rows OK")
PY
python scripts/bench_diff.py BENCH_e2e.json /tmp/BENCH_query_quick.json \
    --require-only --require 'e2e.query_mixed>=1.0'

# serving smoke + gate: sustained walk-LM traffic through the
# ServeRuntime (snapshot corpus -> hot-graph cache -> continuous
# batching; tests/test_runtime.py and tests/test_corpus.py run in the
# main lane above).  The floor pins a served request to never cost
# more than the naive reload-per-request + solo-decode answer — if the
# runtime's cache/batching path rots, it shows up here.
python -m benchmarks.serve_walks --quick --json /tmp/BENCH_serve_quick.json
python - <<'PY'
import json
rows = json.load(open("/tmp/BENCH_serve_quick.json"))
assert rows and all(set(r) == {"name", "seconds", "mb", "speedup"}
                    for r in rows), rows
names = {r["name"] for r in rows}
assert {"e2e.serve_walks_tokens", "e2e.serve_resume"} <= names, names
print(f"serve benchmark json: {len(rows)} rows OK")
PY
python scripts/bench_diff.py BENCH_e2e.json /tmp/BENCH_serve_quick.json \
    --require-only --require 'e2e.serve_walks_tokens>=1.0'

# chaos lane: the seeded fault matrix (scripts/chaos_matrix.py;
# docs/robustness.md).  Four local scenarios — transient-retry bitwise
# parity, stuck-reader StageTimeout within the watchdog budget,
# corrupt-frame quarantine + swap-on-disk recovery, and the SIGTERM
# cursor-resume churn contract — plus the sharded lane: a shard whose
# in-span retries exhaust re-executes its byte span bitwise-equal to
# the fault-free load, under 4 forced CPU host devices.  Every
# scenario is timeout-wrapped: a recovery path that hangs is a
# failure, not a stall.
timeout --kill-after=30 600 python scripts/chaos_matrix.py --seed 7
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    timeout --kill-after=30 600 \
    python scripts/chaos_matrix.py --scenario shard-reexec --seed 7

echo "verify: all green"
