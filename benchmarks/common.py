"""Shared benchmark utilities: datasets, timing, CSV output."""
from __future__ import annotations

import functools
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.core import make_graph_file  # noqa: E402

_CACHE = os.environ.get("REPRO_BENCH_CACHE",
                        os.path.join(tempfile.gettempdir(), "repro_bench"))

# Stand-ins for the paper's Table 1 graph classes, scaled to this host.
# (SuiteSparse is unavailable offline; shapes match the classes' character:
#  web = power-law high degree, social = uniform-ish denser, road = grid.)
DATASETS = {
    "web_rmat": dict(kind="rmat", scale=15, edge_factor=16),      # ~524k edges
    "social_uniform": dict(kind="uniform", scale=15, edge_factor=8),
    "road_grid": dict(kind="grid", scale=16, edge_factor=0),
    # small twin of web_rmat for --quick smoke runs (verify.sh)
    "quick_rmat": dict(kind="rmat", scale=12, edge_factor=8),     # ~32k edges
}


@functools.lru_cache(maxsize=None)
def dataset(name: str, weighted: bool = False):
    os.makedirs(_CACHE, exist_ok=True)
    spec = DATASETS[name]
    path = os.path.join(
        _CACHE, f"{name}{'_w' if weighted else ''}.el")
    meta = path + ".meta"
    if not (os.path.exists(path) and os.path.exists(meta)):
        v, e = make_graph_file(path, spec["kind"], scale=spec["scale"],
                               edge_factor=spec["edge_factor"],
                               weighted=weighted, seed=42)
        with open(meta, "w") as f:
            f.write(f"{v} {e}")
    v, e = (int(x) for x in open(meta).read().split())
    return path, v, e


def require_cpu_backend(what: str) -> None:
    """Refuse to start ``what``, a child process on forced CPU devices,
    unless this process runs on the CPU backend too.  On an accelerator
    host the child's numbers would be CPU timings under the sharded
    rows' names, and a parent that has used JAX already holds the chip."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{what} times forced CPU devices in a child process; this "
            f"process runs on {backend!r}, so its rows would not measure "
            f"the {backend} mesh.  Run it with JAX_PLATFORMS=cpu.")


def timeit(fn, *, repeat: int = 3, warmup: int = 1) -> float:
    """Median seconds over `repeat` runs (paper averages 5; we use
    median-of-3 to bound suite runtime)."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def emit(name: str, seconds: float, derived: str = ""):
    """CSV row: name,us_per_call,derived."""
    print(f"{name},{seconds * 1e6:.1f},{derived}")
