"""End-to-end ``load_csr``: streaming fused device engine vs the old
batch round-trip pipeline vs binary snapshots, same input.

The baseline below reproduces the pre-loader device path verbatim:
synchronous block staging, jitted parse, per-batch compaction
(``_compact_edges``, the historical ``parse.compact_edges`` kept here
as part of the frozen baseline), a device->host copy of every batch,
``np.concatenate``, a host EdgeList, and only then a device CSR build —
all at the historical fixed geometry (beta=256 KiB, batch_blocks=8,
padded tail batch).  The streaming path
(``loader.load_csr(engine="device")``) double-buffers arena staging
behind one fused parse+accumulate program per batch (donated in-place
accumulators, remainder-sized tail batch) that feeds the CSR build
directly; the ``_tuned`` row additionally lets ``core.tune``'s measured
per-host profile pick the block geometry (full runs only — the first
run on a host pays the sweep, later runs hit its cache).

The snapshot rows measure GVEL's "write once, load many" story: the
same graph converted once to a ``.gvel`` binary snapshot
(``core.snapshot``), then loaded with zero parsing — either packed
edgelist sections feeding the device CSR build (``snapshot_el``), or an
embedded prebuilt CSR served straight from mmap (``snapshot_csr``).

The compressed rows measure the trade the codec layer (``core.codecs``)
buys: bytes on disk vs load time, with decompression overlapped with
the parse in the prefetch thread (gzip / framed-zlib text in the
streaming engine, zlib-framed ``.gvel`` v2 sections in the snapshot
engine).  Each row's ``mb=`` field is its input's size on disk, so the
ratio/throughput trade-off is measured, not asserted.

The lazy rows measure what the ``GraphSource`` front door buys on a
*both-sections* compressed snapshot: the old eager reader
(``read_snapshot(path)``) decompresses and checksums the edgelist AND
CSR sections at open, while ``open_graph(path).csr()`` decodes only
the CSR sections (per-section lazy decompression, this PR's ROADMAP
item).

The build row (``e2e.csr_build_binned``) isolates the CSR build on the
loader-shaped packed device arrays (parse excluded): the sort-free
binned build (``build.csr_binned``, propagation-blocking-style
cumulative-count ranks) vs the rank-based staged build it replaces as
the fast path.  Its ``speedup`` field is staged/binned — not the
baseline axis — so the verify.sh floor pins "binned never slower than
staged" directly.

The sharded rows measure the byte-range-sharded streaming load
(``core.distributed.load_csr_sharded_stream`` /
``GraphSource.csr_sharded``) at d=2 and d=4, in one subprocess forced
to 4 CPU host devices.  XLA splits the host threadpool across forced
devices, so the subprocess re-times single-device streaming and the
d=1 sharded pipeline under the same split, and each sharded row's
``speedup`` field is its gain over the frozen batch-roundtrip
baseline *like every other row*, chained through that same-split
streaming time (``t_old/t_streaming x t_streaming_same_split/t_dN``)
so the cross-process normalization is measured, not assumed.  The
derived fields carry the raw same-split diagnostics
(``vs_stream_same_cfg``, ``vs_sharded_d1``, ``cores``): on a
single-core container forced host devices execute serially and d>1
does strictly more total work than d=1 (the exchange is extra), so
those ratios sit below 1.0 by construction — real scaling needs real
cores, the same caveat ``benchmarks/fig9_scaling.py`` documents for
its worker sweep.  The gate in scripts/verify.sh
(``e2e.load_csr_sharded_d4 >= 1.0``) pins the sharded path to the
baseline axis, which catches genuine work regressions: a
retrace-per-load bug in the exchange showed up at ~0.14x on this
metric before being fixed.

``--quick`` (used by scripts/verify.sh) runs the same pipeline on a
small graph with repeat=1 so the benchmark code itself cannot rot
unexecuted.  ``--json OUT.json`` additionally writes machine-readable
rows ``{name, seconds, mb, speedup}`` — ``mb`` is the input's size on
disk and ``speedup`` is this row's gain over the batch-roundtrip
baseline row (baseline = 1.0) — so the perf trajectory is diffable
across PRs.
"""
import gzip
import json
import os
import sys

import numpy as np

from .common import dataset, emit, require_cpu_backend, timeit


def _compact_edges(src_b, dst_b, w_b, counts, total_cap):
    """The historical ``parse.compact_edges`` (deleted from the library
    when the fused ``parse_accumulate`` replaced it), preserved verbatim
    so the baseline row keeps measuring the pre-loader pipeline."""
    import jax.numpy as jnp
    nb, cap = src_b.shape
    starts = jnp.cumsum(counts) - counts
    within = jnp.arange(cap, dtype=jnp.int32)[None, :]
    valid = within < counts[:, None]
    dest = jnp.where(valid, starts[:, None] + within, total_cap)
    dest = dest.reshape(-1)
    out_src = jnp.full((total_cap,), -1, jnp.int32).at[dest].set(
        src_b.reshape(-1), mode="drop")
    out_dst = jnp.full((total_cap,), -1, jnp.int32).at[dest].set(
        dst_b.reshape(-1), mode="drop")
    out_w = None
    if w_b is not None:
        out_w = jnp.zeros((total_cap,), jnp.float32).at[dest].set(
            w_b.reshape(-1), mode="drop")
    return out_src, out_dst, out_w, jnp.sum(counts)


def _batch_roundtrip_csr(path, v, *, beta=256 * 1024, overlap=64,
                         batch_blocks=8):
    """The old pipeline: per-batch host round-trip + EdgeList detour."""
    import jax.numpy as jnp
    from repro.core.blocks import owned_range, plan_blocks, stage_blocks
    from repro.core.csr import convert_to_csr
    from repro.core.parse import parse_blocks
    from repro.core.types import EdgeList

    data = np.memmap(path, dtype=np.uint8, mode="r")
    plan = plan_blocks(len(data), beta=beta, overlap=overlap)
    os_, oe = owned_range(plan)
    edge_cap = plan.edge_cap
    total_cap = batch_blocks * edge_cap
    chunks_src, chunks_dst = [], []
    total = 0
    for start in range(0, plan.num_blocks, batch_blocks):
        ids = np.arange(start, min(start + batch_blocks, plan.num_blocks))
        bufs = stage_blocks(data, plan, ids)
        if len(ids) < batch_blocks:
            pad = np.full((batch_blocks - len(ids), plan.buf_len), 10, np.uint8)
            bufs = np.concatenate([bufs, pad])
        ostart = jnp.full((batch_blocks,), os_, jnp.int32)
        oend = jnp.full((batch_blocks,), oe, jnp.int32)
        src_b, dst_b, w_b, counts = parse_blocks(
            jnp.asarray(bufs), ostart, oend,
            weighted=False, base=1, edge_cap=edge_cap)
        src, dst, w, n = _compact_edges(src_b, dst_b, w_b, counts, total_cap)
        n = int(n)
        chunks_src.append(np.asarray(src[:n]))     # device -> host, every batch
        chunks_dst.append(np.asarray(dst[:n]))
        total += n
    el = EdgeList(np.concatenate(chunks_src), np.concatenate(chunks_dst),
                  None, np.int64(total), v)
    return convert_to_csr(el, method="staged", rho=4)


def _snapshots(path, v):
    """Convert the benchmark graph to .gvel once (cached beside it):
    an edgelist-only snapshot and a CSR-embedded one."""
    from repro.core import convert_to_csr, load_edgelist, save_snapshot

    el_snap, csr_snap = path + ".el.gvel", path + ".csr.gvel"
    if not (os.path.exists(el_snap) and os.path.exists(csr_snap)):
        el = load_edgelist(path, engine="numpy", num_vertices=v)
        save_snapshot(el_snap, edgelist=el)
        save_snapshot(csr_snap, edgelist=el,
                      csr=convert_to_csr(el, method="staged", rho=4))
    return el_snap, csr_snap


def _compressed(path, v):
    """Compressed variants of the benchmark inputs (cached beside them):
    gzip text, framed-zlib text, and a zlib-compressed CSR snapshot."""
    from repro.core import (compress_file_framed, convert_to_csr,
                            load_edgelist, save_snapshot)

    gz, fz, zsnap = path + ".gz", path + ".elz", path + ".z.gvel"
    if not os.path.exists(gz):
        with open(path, "rb") as fin, open(gz, "wb") as fout:
            fout.write(gzip.compress(fin.read(), 6))
    if not os.path.exists(fz):
        compress_file_framed(path, fz, codec="zlib")
    if not os.path.exists(zsnap):
        el = load_edgelist(path, engine="numpy", num_vertices=v)
        save_snapshot(zsnap, edgelist=el,
                      csr=convert_to_csr(el, method="staged", rho=4),
                      compress="zlib")
    return gz, fz, zsnap


def _mb(path):
    return f"mb={os.path.getsize(path) / 1e6:.2f}"


def _build_times(path, v, repeat):
    """(staged, binned) build-only seconds on the same packed device
    arrays the streaming loader hands the build — loader-shaped input
    (pow-2 capacity, ``-1`` padding), parse excluded, so the row
    isolates the CSR build the binned method replaces."""
    import jax
    import jax.numpy as jnp
    from repro.core import load_edgelist
    from repro.core.build import csr_binned, csr_staged

    el = load_edgelist(path, engine="numpy", num_vertices=v)
    n = int(el.num_edges)
    cap = 1 << max(n - 1, 1).bit_length()
    src = np.full(cap, -1, np.int32)
    dst = np.full(cap, -1, np.int32)
    src[:n] = np.asarray(el.src[:n])
    dst[:n] = np.asarray(el.dst[:n])
    bsrc, bdst = jnp.asarray(src), jnp.asarray(dst)
    t_staged = timeit(lambda: jax.block_until_ready(
        csr_staged(bsrc, bdst, None, v, rho=4)), repeat=repeat)
    t_binned = timeit(lambda: jax.block_until_ready(
        csr_binned(bsrc, bdst, None, v)), repeat=repeat)
    return t_staged, t_binned


_SHARDED_CODE = """
import json, sys, time
import numpy as np, jax
from repro.core import open_graph
from repro.core.compat import device_mesh
from repro.core.distributed import load_csr_sharded_stream

path, v, repeat = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])

def best_of(fn, repeat):
    fn()                                  # compile warmup
    b = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter(); fn(); b = min(b, time.perf_counter() - t0)
    return b

out = {"stream": best_of(
    lambda: open_graph(path, engine="device", num_vertices=v).csr(), repeat)}
for d in (1, 2, 4):
    mesh = device_mesh(np.array(jax.devices()[:d]), ("data",))
    out[f"d{d}"] = best_of(
        lambda: load_csr_sharded_stream(mesh, "data", path, num_vertices=v),
        repeat)
print("SHARDED_JSON " + json.dumps(out))
"""


def _sharded_times(path, v, repeat):
    """(stream, d1, d2, d4) seconds, all measured in one subprocess under
    ``--xla_force_host_platform_device_count=4`` so the threadpool split
    is identical across the four timings."""
    import subprocess

    require_cpu_backend("the sharded e2e rows")

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_CODE, path, str(v), str(repeat)],
        env=env, capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded benchmark subprocess failed:\n"
                           f"{proc.stdout}\n{proc.stderr}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("SHARDED_JSON ")][-1]
    t = json.loads(line[len("SHARDED_JSON "):])
    return t["stream"], t["d1"], t["d2"], t["d4"]


def run(quick: bool = False, json_path: str = None):
    from repro.core import get_engine, open_graph, read_snapshot

    path, v, e = dataset("quick_rmat" if quick else "web_rmat")
    repeat = 1 if quick else 3
    el_snap, csr_snap = _snapshots(path, v)
    gz, fz, zsnap = _compressed(path, v)
    snap_eng = get_engine("snapshot")

    def cold(p, **kw):
        # measure a fresh open (validation + any decompression), not a
        # hit on the engine's stat-validated in-process memo; every row
        # goes through the GraphSource front door
        snap_eng.clear_memo()
        return open_graph(p, engine="snapshot", num_vertices=v).csr(**kw)

    def stream_csr(p, **kw):
        return open_graph(p, engine="device",
                          num_vertices=v, **kw).csr(method="staged")

    def eager_zsnap_csr():
        # the pre-GraphSource contract: read_snapshot() decompresses and
        # checksums EVERY section at open, edgelist included
        snap_eng.clear_memo()
        return read_snapshot(zsnap).csr()

    t_old = timeit(lambda: _batch_roundtrip_csr(path, v), repeat=repeat)
    t_new = timeit(lambda: stream_csr(path), repeat=repeat)
    # measured per-host geometry (core.tune); quick mode skips it so
    # verify.sh never pays a tuning sweep
    t_tuned = None if quick else timeit(
        lambda: stream_csr(path, tune=True), repeat=repeat)
    t_sel = timeit(lambda: cold(el_snap, method="staged"), repeat=repeat)
    t_scsr = timeit(lambda: cold(csr_snap), repeat=repeat)
    t_gz = timeit(lambda: stream_csr(gz), repeat=repeat)
    t_fz = timeit(lambda: stream_csr(fz), repeat=repeat)
    t_zeager = timeit(eager_zsnap_csr, repeat=repeat)
    t_zlazy = timeit(lambda: cold(zsnap), repeat=repeat)

    rows = []

    def row(name, seconds, in_path, derived=""):
        emit(name, seconds, derived + (";" if derived else "") + _mb(in_path))
        rows.append({"name": name, "seconds": round(seconds, 6),
                     "mb": round(os.path.getsize(in_path) / 1e6, 3),
                     "speedup": round(t_old / seconds, 2)})

    row("e2e.load_csr_batch_roundtrip", t_old, path,
        f"edges_per_s={e / t_old:.3e}")
    row("e2e.load_csr_streaming", t_new, path,
        f"edges_per_s={e / t_new:.3e};speedup={t_old / t_new:.2f}x")
    if t_tuned is not None:
        row("e2e.load_csr_streaming_tuned", t_tuned, path,
            f"edges_per_s={e / t_tuned:.3e};vs_default={t_new / t_tuned:.2f}x")
    row("e2e.load_csr_snapshot_el", t_sel, el_snap,
        f"edges_per_s={e / t_sel:.3e};vs_streaming={t_new / t_sel:.2f}x")
    row("e2e.load_csr_snapshot_csr", t_scsr, csr_snap,
        f"edges_per_s={e / t_scsr:.3e};vs_streaming={t_new / t_scsr:.2f}x")
    row("e2e.load_csr_text_gz", t_gz, gz,
        f"edges_per_s={e / t_gz:.3e};vs_raw_text={t_new / t_gz:.2f}x")
    row("e2e.load_csr_text_framed_zlib", t_fz, fz,
        f"edges_per_s={e / t_fz:.3e};vs_raw_text={t_new / t_fz:.2f}x")
    # both-sections compressed snapshot, cold .csr(): eager decodes the
    # edgelist frames it never serves, lazy decodes CSR sections only
    row("e2e.load_csr_snapshot_zlib_eager", t_zeager, zsnap,
        f"edges_per_s={e / t_zeager:.3e}")
    row("e2e.load_csr_snapshot_zlib_lazy", t_zlazy, zsnap,
        f"edges_per_s={e / t_zlazy:.3e};vs_eager={t_zeager / t_zlazy:.2f}x")
    # build-only row: binned vs staged on the loader-shaped packed
    # arrays.  Unlike the load rows, speedup here is staged/binned — the
    # verify.sh floor (>= 1.0) pins the binned build to never regress
    # behind the staged build it's meant to beat.
    t_staged_b, t_binned_b = _build_times(path, v, repeat)
    emit("e2e.csr_build_binned", t_binned_b,
         f"edges_per_s={e / t_binned_b:.3e};"
         f"vs_staged={t_staged_b / t_binned_b:.2f}x;" + _mb(path))
    rows.append({"name": "e2e.csr_build_binned",
                 "seconds": round(t_binned_b, 6),
                 "mb": round(os.path.getsize(path) / 1e6, 3),
                 "speedup": round(t_staged_b / t_binned_b, 2)})
    # sharded rows: speedup is vs the batch-roundtrip baseline like every
    # other row, chained through the same-split streaming re-timing so
    # the subprocess threadpool split is normalized out (module docstring)
    t_s1, t_sd1, t_d2, t_d4 = _sharded_times(path, v, repeat)
    for name, secs in (("e2e.load_csr_sharded_d2", t_d2),
                       ("e2e.load_csr_sharded_d4", t_d4)):
        emit(name, secs,
             f"edges_per_s={e / secs:.3e};"
             f"vs_stream_same_cfg={t_s1 / secs:.2f}x;"
             f"vs_sharded_d1={t_sd1 / secs:.2f}x;"
             f"cores={os.cpu_count()};" + _mb(path))
        rows.append({"name": name, "seconds": round(secs, 6),
                     "mb": round(os.path.getsize(path) / 1e6, 3),
                     "speedup": round((t_old / t_new) * (t_s1 / secs), 2)})
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
            sys.exit("usage: python -m benchmarks.e2e_load_csr "
                     "[--quick] [--json OUT.json]")
        out = argv[i + 1]
    run(quick="--quick" in argv, json_path=out)
