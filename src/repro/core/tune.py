"""Measured block-geometry autotuning for the streaming loader.

GVEL's Figure 2 sweeps the block size and finds the throughput knee
empirically — the right ``beta`` (owned bytes per block) and
``batch_blocks`` (blocks per jitted program) depend on the host's cache
hierarchy, core count, and XLA backend, not on anything we can derive
statically.  This module replaces the loader's historical
``beta=256 KiB, batch_blocks=8`` magic numbers with the same idea:

* :func:`run_sweep` stages a synthetic in-memory edgelist through the
  *actual* fused streaming step (``blocks`` staging +
  ``parse.parse_accumulate``) for every ``beta x batch_blocks`` combo
  and times it (compile excluded by a warmup pass per combo);
* :func:`tuned_geometry` memoizes the sweep winner in a per-host JSON
  profile — ``$REPRO_TUNE_CACHE`` or ``~/.cache/repro/tune.json`` —
  keyed by :func:`host_key`, so the sweep runs once per host, not once
  per process;
* the loader consults it only when asked (``open_graph(path,
  tune=True)`` / ``LoadOptions(tune=True)``); explicit
  ``beta``/``batch_blocks`` in ``engine_kw`` always win.

``python -m benchmarks.tune_sweep`` runs the sweep standalone and emits
the rows as JSON (the Fig. 2 reproduction artifact); delete the cache
file (or pass ``refresh=True``) to re-measure after a hardware or
jax upgrade.  See docs/performance.md for the full tuning guide.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, Iterable, List, Optional

import numpy as np

PROFILE_VERSION = 1
DEFAULT_BETAS = (64 * 1024, 256 * 1024, 1024 * 1024)
DEFAULT_BATCH_BLOCKS = (2, 4, 8)
SAMPLE_BYTES = 4 * 1024 * 1024
_ENV_CACHE = "REPRO_TUNE_CACHE"


def host_key() -> str:
    """Profile key: geometry is a property of this machine + the
    resolved platform configuration (:func:`repro.core.env.fingerprint`
    — backend, forced device count and float width all move the knee,
    so each gets its own profile)."""
    from .env import fingerprint
    return fingerprint()


def cache_path() -> str:
    env = os.environ.get(_ENV_CACHE)
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "tune.json")


def clear_cache(path: Optional[str] = None) -> bool:
    """Delete the profile file (next tuned load re-measures).  Returns
    whether a file was removed."""
    p = path or cache_path()
    try:
        os.remove(p)
        return True
    except FileNotFoundError:
        return False


def synthetic_sample(nbytes: int = SAMPLE_BYTES, *, weighted: bool = False,
                     seed: int = 0) -> np.ndarray:
    """An in-memory uniform edgelist of ~``nbytes`` text bytes — the
    sweep's workload proxy (per-host profile, not per-file: the parse
    cost depends on bytes/line shape far more than on graph structure).
    """
    rng = np.random.default_rng(seed)
    # ~"123456 654321[ 0.123]\n" -> estimate lines from the line width
    width = 14 + (6 if weighted else 0)
    n = max(nbytes // width, 16)
    src = rng.integers(1, 999_999, n)
    dst = rng.integers(1, 999_999, n)
    if weighted:
        w = (rng.random(n) * 9).round(3)
        lines = [f"{s} {d} {x}" for s, d, x in zip(src, dst, w)]
    else:
        lines = [f"{s} {d}" for s, d in zip(src, dst)]
    return np.frombuffer(("\n".join(lines) + "\n").encode(), np.uint8)


def measure_geometry(data: np.ndarray, beta: int, batch_blocks: int, *,
                     weighted: bool = False, base: int = 1,
                     overlap: int = 64, repeat: int = 2) -> float:
    """Seconds for one full fused streaming pass over ``data`` at this
    geometry (min over ``repeat`` passes after one compile warmup)."""
    import jax
    import jax.numpy as jnp

    from .blocks import MemoryBlockSource, owned_range, plan_blocks
    from .parse import make_accumulators, parse_accumulate

    plan = plan_blocks(len(data), beta=beta, overlap=overlap)
    os_, oe = owned_range(plan)
    edge_cap = plan.edge_cap
    cap = plan.num_blocks * edge_cap
    num_batches = -(-plan.num_blocks // batch_blocks)
    source = MemoryBlockSource(data)

    def one_pass() -> None:
        acc_src, acc_dst, acc_w, total = make_accumulators(
            cap, weighted=weighted)
        for i in range(num_batches):
            start = i * batch_blocks
            ids = np.arange(start, min(start + batch_blocks,
                                       plan.num_blocks))
            bufs = source.stage(plan, ids)
            nb = bufs.shape[0]
            acc_src, acc_dst, acc_w, total = parse_accumulate(
                acc_src, acc_dst, acc_w, total, jnp.asarray(bufs),
                jnp.full((nb,), os_, jnp.int32),
                jnp.full((nb,), oe, jnp.int32),
                weighted=weighted, base=base, edge_bound=nb * edge_cap)
        jax.block_until_ready(total)

    one_pass()                                    # compile both programs
    best = float("inf")
    for _ in range(max(repeat, 1)):
        t0 = time.perf_counter()
        one_pass()
        best = min(best, time.perf_counter() - t0)
    return best


def run_sweep(data: Optional[np.ndarray] = None, *,
              betas: Iterable[int] = DEFAULT_BETAS,
              batch_blocks: Iterable[int] = DEFAULT_BATCH_BLOCKS,
              weighted: bool = False, base: int = 1, overlap: int = 64,
              sample_bytes: int = SAMPLE_BYTES,
              repeat: int = 2) -> List[Dict]:
    """Measure every ``beta x batch_blocks`` combo; rows sorted fastest
    first.  ``data=None`` measures on :func:`synthetic_sample`."""
    if data is None:
        data = synthetic_sample(sample_bytes, weighted=weighted)
    rows = []
    for beta in betas:
        if beta <= overlap:
            continue                      # plan_blocks would reject it
        for bb in batch_blocks:
            secs = measure_geometry(data, int(beta), int(bb),
                                    weighted=weighted, base=base,
                                    overlap=overlap, repeat=repeat)
            rows.append({"beta": int(beta), "batch_blocks": int(bb),
                         "seconds": round(secs, 6),
                         "mb_per_s": round(len(data) / 1e6 / secs, 3)})
    if not rows:
        raise ValueError("empty sweep grid (every beta <= overlap?)")
    rows.sort(key=lambda r: r["seconds"])
    return rows


def best_geometry(rows: List[Dict]) -> Dict[str, int]:
    top = min(rows, key=lambda r: r["seconds"])
    return {"beta": top["beta"], "batch_blocks": top["batch_blocks"]}


def _load_profile(path: str) -> Dict:
    try:
        with open(path) as f:
            prof = json.load(f)
        if isinstance(prof, dict) and prof.get("version") == PROFILE_VERSION:
            return prof
    except (OSError, ValueError):
        pass                               # absent or corrupt: re-measure
    return {"version": PROFILE_VERSION, "hosts": {}}


def _save_profile(path: str, prof: Dict) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(prof, f, indent=2)
        f.write("\n")
    os.replace(tmp, path)                  # atomic: readers never see half


def _slot_name(weighted: bool, shards: int) -> str:
    """Profile slot: weighted/unweighted, with a ``_d{shards}`` suffix
    for the sharded streaming path (each shard streams ~1/d of the file
    with d parse pipelines contending for the same cores, so its knee
    sits elsewhere than the single-stream one)."""
    slot = "weighted" if weighted else "unweighted"
    if shards > 1:
        slot = f"{slot}_d{int(shards)}"
    return slot


def save_geometry(rows: List[Dict], *, weighted: bool = False,
                  shards: int = 1,
                  path: Optional[str] = None) -> Dict[str, int]:
    """Persist a sweep's winner (plus the full rows) into this host's
    profile slot; returns the winner.  The single place the profile
    entry schema is written — :func:`tuned_geometry` and
    ``benchmarks/tune_sweep.py --apply`` both go through it.  The
    profile is re-read immediately before the atomic replace, so a
    concurrent process persisting the *other* weighted/unweighted slot
    (its sweep takes tens of seconds; this read+write, microseconds) is
    not silently discarded."""
    p = path or cache_path()
    best = best_geometry(rows)
    prof = _load_profile(p)
    prof["hosts"].setdefault(host_key(), {})[_slot_name(weighted, shards)] = {
        **best, "sweep": rows, "measured_at": int(time.time())}
    _save_profile(p, prof)
    return best


def tuned_geometry(*, weighted: bool = False, shards: int = 1,
                   refresh: bool = False, **sweep_kw) -> Dict[str, int]:
    """The measured ``{"beta": ..., "batch_blocks": ...}`` for this host.

    Loads the per-host JSON profile; on a miss (or ``refresh=True``)
    runs :func:`run_sweep` once — tens of seconds of compile+measure —
    and persists the winner alongside the full sweep rows.  Weighted
    and unweighted parses are profiled separately (the weighted program
    does more work per byte), and each shard count gets its own slot
    (``shards`` d>1 measures on a ~1/d sample — the span one of d
    byte-range shards would stream).
    """
    path = cache_path()
    key, slot = host_key(), _slot_name(weighted, shards)
    prof = _load_profile(path)
    entry = prof["hosts"].get(key, {}).get(slot)
    if entry and not refresh:
        return {"beta": int(entry["beta"]),
                "batch_blocks": int(entry["batch_blocks"])}
    if shards > 1:
        sweep_kw.setdefault(
            "sample_bytes", max(SAMPLE_BYTES // int(shards), 256 * 1024))
    rows = run_sweep(weighted=weighted, **sweep_kw)
    return save_geometry(rows, weighted=weighted, shards=shards, path=path)
