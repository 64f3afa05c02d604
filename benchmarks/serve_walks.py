"""Graph-walk serving benchmark: sustained walk-LM traffic against a
snapshot-backed corpus through the ServeRuntime, vs a naive
reload-per-request baseline, plus the corpus resume-vs-replay payoff.

The end-to-end scenario the loader exists for (ROADMAP open item 2):
requests name a graph; the runtime resolves it through the hot-graph
cache (open/validate once, mtime-revalidated per request), derives a
deterministic walk prompt from the CSR, and decodes with continuous
batching — slots shared across requests, freed slots refilled the same
tick.  The baseline answers the same request stream the pre-runtime
way: reopen the snapshot and materialize the full CSR **per request**,
then decode alone on a single-slot engine (no batching, no handle
reuse), timed on a sample and scaled.

Rows (``{name, seconds, mb, speedup}``; ``mb`` = snapshot size):

* ``e2e.serve_naive`` — the scaled reload-per-request baseline (1.0x).
* ``e2e.serve_walks_tokens`` — the served stream; ``speedup`` is
  naive-per-request / served-per-request.  verify.sh gates it >= 1.0:
  if serving a request through the runtime ever costs more than a
  cold reload + solo decode, the serving path has rotted.
* ``e2e.serve_resume`` — producing corpus batches [k, k+m) by resuming
  at the checkpointed cursor vs replaying a sequential stream from 0
  (what a non-step-indexed pipeline must do after a kill).

``--quick`` (used by scripts/verify.sh) runs the same pipeline on the
small corpus + reduced model so the serving code cannot rot
unexecuted; ``--json OUT.json`` writes the machine-readable rows.
The run also prints ``runtime.stats()`` — requests/tokens/s, batch
occupancy, cache + frame-cache hits — the subsystem's observability
surface (docs/serving.md).
"""
import json
import os
import sys

import numpy as np

from .common import dataset, emit, timeit


def _snapshot(quick):
    from repro.core import convert_to_csr, load_edgelist, save_snapshot

    path, v, e = dataset("quick_rmat" if quick else "web_rmat")
    gv = path + ".serve.gvel"
    if not os.path.exists(gv):
        el = load_edgelist(path, engine="numpy", num_vertices=v)
        csr = convert_to_csr(el)
        save_snapshot(gv, edgelist=el, csr=csr)
    return gv, v, e


def _naive_per_request(cfg, params, gv, v, rids, *, prompt_len, max_new):
    """Reload-per-request baseline: fresh open + FULL CSR + solo
    batch=1 decode, no cache, no batching."""
    import jax.numpy as jnp

    from repro.core import get_engine, open_graph
    from repro.data.walks import I32, random_walks
    from repro.serve.engine import Request, ServeEngine

    eng = ServeEngine(cfg, params, batch=1, max_seq=64)
    snap_engine = get_engine("snapshot")

    def one(rid):
        snap_engine.clear_memo()
        csr = open_graph(gv).csr()         # cold full load, every request
        import jax
        walk = random_walks(jnp.asarray(np.asarray(csr.offsets), I32),
                            jnp.asarray(np.asarray(csr.targets), I32),
                            jax.random.key(0), num_walks=1,
                            length=prompt_len, num_vertices=v,
                            walk_offset=rid)
        prompt = np.asarray(walk[0] % cfg.vocab_size, np.int32)
        eng.submit(Request(rid, prompt, max_new))
        eng.run()

    total = timeit(lambda: [one(r) for r in rids], repeat=1, warmup=1)
    return total / len(rids)


def run(quick: bool = False, json_path: str = None):
    import jax

    from repro.configs import reduced_config
    from repro.core.cache import SourceCache
    from repro.data.corpus import CorpusConfig, WalkCorpus
    from repro.core.source import open_graph
    from repro.ft.coordinator import FTConfig
    from repro.models import init_params
    from repro.serve.runtime import ServeRuntime

    gv, v, e = _snapshot(quick)
    mb = os.path.getsize(gv) / 1e6
    cfg = reduced_config("phi4-mini-3.8b")
    params = init_params(jax.random.key(0), cfg)

    n_req = 16 if quick else 48
    prompt_len, max_new = 6, 8 if quick else 16
    ft = FTConfig(straggler_policy="degrade", straggler_factor=16.0,
                  straggler_window=8)

    def runtime():
        return ServeRuntime(cfg, params, batch=4, max_seq=64,
                            cache=SourceCache(capacity=4), ft=ft,
                            prompt_len=prompt_len)

    # warm the jit caches (prefill + decode + walk shapes) off the clock
    runtime().serve([gv] * 4, max_new=max_new)

    rt = runtime()
    t_served = timeit(lambda: rt.serve([gv] * n_req, max_new=max_new),
                      repeat=1, warmup=0)
    st = rt.stats()
    served_per_req = t_served / n_req

    naive = _naive_per_request(cfg, params, gv, v,
                               list(range(3 if quick else 6)),
                               prompt_len=prompt_len, max_new=max_new)

    # corpus resume-vs-replay: batches [k, k+m) from the cursor vs a
    # sequential replay from 0 (non-step-indexed restart)
    cc = CorpusConfig(batch=8, seq=32, vocab_size=cfg.vocab_size, seed=5)
    corpus = WalkCorpus(open_graph(gv), cc)
    k, m = (16, 4) if quick else (64, 8)
    corpus.batch_at(0)                     # warm walk jit for this shape

    def consume(start, count):
        with corpus.batches(start) as stream:
            for _ in range(count):
                next(stream)

    t_replay = timeit(lambda: consume(0, k + m), repeat=1, warmup=0)
    t_resume = timeit(lambda: consume(k, m), repeat=1, warmup=0)

    rows = []

    def row(name, seconds, speedup, derived=""):
        emit(name, seconds, derived + (";" if derived else "") + f"mb={mb:.2f}")
        rows.append({"name": name, "seconds": round(seconds, 6),
                     "mb": round(mb, 3), "speedup": round(speedup, 2)})

    toks = st["tokens"]
    row("e2e.serve_naive", naive * n_req, 1.0,
        f"per_req={naive * 1e6:.0f}us;scaled_from_sample")
    row("e2e.serve_walks_tokens", t_served, naive / served_per_req,
        f"n={n_req};tokens={toks};tok_per_s={toks / t_served:.1f};"
        f"req_per_s={n_req / t_served:.2f};occupancy={st['occupancy']};"
        f"vs_naive={naive / served_per_req:.1f}x")
    row("e2e.serve_resume", t_resume, t_replay / t_resume,
        f"k={k};m={m};replay={t_replay:.3f}s;"
        f"vs_replay={t_replay / t_resume:.1f}x")
    print(f"runtime.stats: {json.dumps(st)}")
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
            sys.exit("usage: python -m benchmarks.serve_walks "
                     "[--quick] [--json OUT.json]")
        out = argv[i + 1]
    run(quick="--quick" in argv, json_path=out)
