"""Paper Fig. 9: strong scaling with worker count (1..16) and, for the
sharded streaming loader, with mesh shard count (1, 2, 4).

Workers are threads over newline-aligned chunks (reading) and over
partition-local sorts (CSR build) — numpy's C kernels release the GIL,
so on a multicore host this scales like the paper's OpenMP loops.  The
shard sweep times ``core.distributed.load_csr_sharded_stream`` over
meshes of 1, 2 and 4 forced host devices inside one subprocess (the
device count is fixed at 4 so XLA's threadpool split is identical
across mesh widths).  This container exposes a single core: the
harness still sweeps both grids and reports the (necessarily flat or
declining) curves; the derived field carries cores_available so the
result is interpretable.  On real cores the shard sweep is the
end-to-end strong-scaling figure — every stage including the parse
runs on the mesh.
"""
import json
import os
import subprocess
import sys

import numpy as np

from .common import dataset, emit, require_cpu_backend, timeit

_SHARD_SWEEP_CODE = """
import json, sys, time
import numpy as np, jax
from repro.core.compat import device_mesh
from repro.core.distributed import load_csr_sharded_stream

path, v = sys.argv[1], int(sys.argv[2])
out = {}
for d in (1, 2, 4):
    mesh = device_mesh(np.array(jax.devices()[:d]), ("data",))
    fn = lambda: load_csr_sharded_stream(mesh, "data", path, num_vertices=v)
    fn()                                   # compile warmup
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter(); fn(); best = min(best, time.perf_counter() - t0)
    out[f"d{d}"] = best
print("SWEEP_JSON " + json.dumps(out))
"""


def _shard_sweep(path, v):
    require_cpu_backend("the fig9 shard sweep")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, "-c", _SHARD_SWEEP_CODE, path, str(v)],
        env=env, capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"shard sweep subprocess failed:\n"
                           f"{proc.stdout}\n{proc.stderr}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("SWEEP_JSON ")][-1]
    return json.loads(line[len("SWEEP_JSON "):])


def run():
    from repro.core import load_edgelist
    from repro.core.build import csr_binned_np, csr_staged_np

    path, v, e = dataset("web_rmat")
    cores = os.cpu_count()
    el = load_edgelist(path, engine="threads", num_vertices=v, num_workers=1)
    n = int(el.num_edges)
    src = np.asarray(el.src[:n])
    dst = np.asarray(el.dst[:n])

    base_el = base_csr = base_bin = None
    for w in [1, 2, 4, 8, 16]:
        t_el = timeit(lambda ww=w: load_edgelist(
            path, engine="threads", num_vertices=v, num_workers=ww), repeat=2)
        t_csr = timeit(lambda ww=w: csr_staged_np(
            src, dst, None, v, rho=max(4, ww), num_workers=ww), repeat=2)
        t_bin = timeit(lambda ww=w: csr_binned_np(
            src, dst, None, v, num_workers=ww), repeat=2)
        base_el = base_el or t_el
        base_csr = base_csr or t_csr
        base_bin = base_bin or t_bin
        emit(f"fig9.edgelist_w{w}", t_el,
             f"speedup={base_el / t_el:.2f}x;cores_available={cores}")
        emit(f"fig9.csr_w{w}", t_csr,
             f"speedup={base_csr / t_csr:.2f}x;cores_available={cores}")
        emit(f"fig9.csr_binned_w{w}", t_bin,
             f"speedup={base_bin / t_bin:.2f}x;cores_available={cores}")

    sweep = _shard_sweep(path, v)
    base = sweep["d1"]
    for d in (1, 2, 4):
        t = sweep[f"d{d}"]
        emit(f"fig9.sharded_d{d}", t,
             f"speedup={base / t:.2f}x;cores_available={cores}")


if __name__ == "__main__":
    run()
