"""Graph query service: thousands of mixed point/range/full requests
against a snapshot corpus, served through the hot-graph cache.

This is the "millions of users" serving scenario (ParaGrapher's
selective-loading motivation) made measurable: production traffic
against a loaded graph is mostly *point reads* — the neighbors of one
vertex, a row range for one worker — not full CSR loads.  The service
path this drives (``repro.core.cache.query``) answers those through

  * a bounded LRU of open ``GraphSource`` handles (open/validate once,
    stat-revalidate per hit), and
  * selective section reads: ``neighbors(v)`` / ``csr(rows=)`` slice
    the mmap'd CSR sections of raw snapshots without touching the rest
    of the file, and decode only the overlapping frames of compressed
    ones (``docs/query.md``).

The workload is a deterministic mixed stream over a corpus of raw and
zlib-compressed both-sections ``.gvel`` snapshots: ~70% point lookups
(``neighbors``/``degree``), ~25% row ranges, and a sprinkle of ``info``
and full-CSR requests.  The baseline (``e2e.query_naive``) is the same
request stream answered the only way the pre-query API allowed — open
the file, materialize the FULL CSR, slice it — timed per-request on a
sample and scaled (running thousands of cold full loads would take
minutes for a number that's constant per request).  ``speedup`` on the
``e2e.query_mixed`` row is naive-per-request / served-per-request; the
verify.sh gate pins it ≥ 1.0 — if serving a point read ever costs more
than a full load, the selective path has rotted.

``--quick`` (used by scripts/verify.sh) runs the same pipeline on a
small corpus so the service code cannot rot unexecuted.  ``--json
OUT.json`` writes machine-readable rows ``{name, seconds, mb,
speedup}`` — ``seconds`` is the whole request stream, ``mb`` the
corpus size on disk — so the perf trajectory is diffable across PRs.
"""
import json
import os
import shutil
import sys

import numpy as np

from .common import dataset, emit, timeit


def _corpus(quick):
    """Raw + zlib both-sections snapshots of the benchmark graph
    (cached beside it); copies give the cache distinct paths."""
    from repro.core import convert_to_csr, load_edgelist, save_snapshot

    path, v, e = dataset("quick_rmat" if quick else "web_rmat")
    raw0, z0 = path + ".qraw.gvel", path + ".qz.gvel"
    if not (os.path.exists(raw0) and os.path.exists(z0)):
        el = load_edgelist(path, engine="numpy", num_vertices=v)
        csr = convert_to_csr(el)
        save_snapshot(raw0, edgelist=el, csr=csr)
        save_snapshot(z0, edgelist=el, csr=csr, compress="zlib")
    paths = [raw0, z0]
    for i in range(1 if quick else 2):         # distinct paths, same graph
        for src in (raw0, z0):
            dup = f"{src}.{i}"
            if not os.path.exists(dup):
                shutil.copyfile(src, dup)
            paths.append(dup)
    return paths, v, e


def _requests(paths, v, n, seed=7):
    """Deterministic mixed stream: ~60% neighbors, ~10% degree,
    ~25% row ranges, ~4% info, ~1% full CSR."""
    rng = np.random.default_rng(seed)
    kinds = rng.choice(
        ["neighbors", "degree", "rows", "info", "csr"], size=n,
        p=[0.60, 0.10, 0.25, 0.04, 0.01])
    which = rng.integers(0, len(paths), size=n)
    verts = rng.integers(0, v, size=n)
    spans = rng.integers(1, max(2, v // 64), size=n)
    reqs = []
    for k, w, u, s in zip(kinds, which, verts, spans):
        if k in ("neighbors", "degree"):
            reqs.append((paths[w], k, int(u), 0))
        elif k == "rows":
            lo = int(u)
            reqs.append((paths[w], k, lo, min(v, lo + int(s))))
        else:
            reqs.append((paths[w], k, 0, 0))
    return reqs


def _serve(cache, reqs):
    for path, op, a, b in reqs:
        if op in ("neighbors", "degree"):
            cache.query(path, op, vertex=a)
        elif op == "rows":
            cache.query(path, "rows", rows=(a, b))
        else:
            cache.query(path, op)


def _naive_per_request(reqs, v, sample):
    """Per-request seconds for the pre-query answer: open, build the
    FULL CSR, slice.  Cold per request — no handle reuse, no partial
    reads — timed on a sample of the same stream."""
    from repro.core import get_engine, open_graph

    eng = get_engine("snapshot")

    def one(path, op, a, b):
        eng.clear_memo()
        csr = open_graph(path, engine="snapshot", num_vertices=v).csr()
        if op == "neighbors":
            csr.targets[csr.offsets[a]:csr.offsets[a + 1]]
        elif op == "degree":
            int(csr.offsets[a + 1]) - int(csr.offsets[a])
        elif op == "rows":
            csr.targets[csr.offsets[a]:csr.offsets[b]]

    picks = reqs[:: max(1, len(reqs) // sample)][:sample]
    total = timeit(lambda: [one(*r) for r in picks], repeat=1, warmup=1)
    return total / len(picks)


def run(quick: bool = False, json_path: str = None):
    from repro.core.cache import SourceCache

    paths, v, e = _corpus(quick)
    n = 2000 if quick else 10000
    reqs = _requests(paths, v, n)
    n_point = sum(1 for r in reqs if r[1] in ("neighbors", "degree"))
    n_range = sum(1 for r in reqs if r[1] == "rows")

    cache = SourceCache(capacity=len(paths))
    t_mixed = timeit(lambda: _serve(cache, reqs), repeat=1 if quick else 3)
    per_req = t_mixed / n
    st = cache.stats()

    # hot point reads only, zlib snapshot: the pure selective-decode path
    zp = [p for p in paths if ".qz." in p][0]
    pts = [(zp, "neighbors", int(u), 0)
           for u in np.random.default_rng(11).integers(0, v, 1000)]
    t_pts = timeit(lambda: _serve(cache, pts), repeat=1 if quick else 3)

    naive = _naive_per_request(reqs, v, sample=5 if quick else 10)

    corpus_mb = sum(os.path.getsize(p) for p in paths) / 1e6
    rows = []

    def row(name, seconds, speedup, derived=""):
        emit(name, seconds,
             derived + (";" if derived else "") + f"mb={corpus_mb:.2f}")
        rows.append({"name": name, "seconds": round(seconds, 6),
                     "mb": round(corpus_mb, 3), "speedup": round(speedup, 2)})

    row("e2e.query_naive", naive * n, 1.0,
        f"per_req={naive * 1e6:.0f}us;scaled_from_sample")
    row("e2e.query_mixed", t_mixed, naive / per_req,
        f"n={n};point={n_point};range={n_range};per_req={per_req * 1e6:.1f}us;"
        f"req_per_s={n / t_mixed:.3e};hits={st['hits']};misses={st['misses']};"
        f"vs_naive={naive / per_req:.1f}x")
    row("e2e.query_neighbors_zlib_hot", t_pts, naive / (t_pts / len(pts)),
        f"n={len(pts)};per_req={t_pts / len(pts) * 1e6:.1f}us;"
        f"req_per_s={len(pts) / t_pts:.3e}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(rows, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    argv = sys.argv[1:]
    out = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            sys.exit("usage: python -m benchmarks.query_service "
                     "[--quick] [--json OUT.json]")
        out = argv[i + 1]
    run(quick="--quick" in argv, json_path=out)
