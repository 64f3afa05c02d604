#!/usr/bin/env python3
"""Smoke run of the text -> CSR load on a TPU, through ``open_graph``.

    python3 chip_smoke.py             # one chip: text load, then .gvel load
    python3 chip_smoke.py --chips 4   # four chips: the sharded mesh load only

Writes a Graph500-spec graph (scale 22, edge factor 16, fixed seed:
4,194,304 vertices, 67,108,864 edges, about 1.1 GB of text) under
``.smoke_data/`` and loads it as a user would.  Every CSR is checked
element by element against the host oracle ``build.csr_np`` built from
the generator's own edges.  The times printed are smoke timings of
single runs, not benchmark results.

The last line of standard output is one JSON object naming the device.
The script exits non-zero, without that line, when JAX finds no TPU or
when any phase fails.  Everything runs in this one process: a child
could not reach a chip that this process holds.
"""
import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SCALE = 22
EDGE_FACTOR = 16
SEED = 1
DATA_DIR = ROOT / ".smoke_data"


def say(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def make_graph(scale: int):
    """Write the text graph; return ``(path, oracle CSR, |V|, |E|)``.

    The same two steps as ``repro.core.make_graph_file``, keeping the
    generator's edges so the oracle never goes through a parser."""
    from repro.core.build import csr_np
    from repro.core.generate import rmat_edges, write_edgelist

    DATA_DIR.mkdir(exist_ok=True)
    path = str(DATA_DIR / f"graph500_s{scale}.el")
    t0 = time.perf_counter()
    src, dst, v = rmat_edges(scale, EDGE_FACTOR, seed=SEED)
    write_edgelist(path, src, dst)
    oracle = csr_np(src, dst, None, v)
    say(f"setup: graph500 scale {scale} edge_factor {EDGE_FACTOR} seed {SEED}: "
        f"V={v} E={len(src)} text={os.path.getsize(path)} bytes, "
        f"written + oracle built in {time.perf_counter() - t0:.1f} s")
    return path, oracle, v, len(src)


def check_csr(name: str, csr, oracle, num_edges: int) -> None:
    import numpy as np

    if csr.num_vertices != oracle.num_vertices:
        fail(f"{name}: num_vertices {csr.num_vertices} != "
             f"{oracle.num_vertices}")
    if not np.array_equal(np.asarray(csr.offsets), oracle.offsets):
        fail(f"{name}: offsets differ from csr_np")
    if not np.array_equal(np.asarray(csr.targets), oracle.targets):
        fail(f"{name}: targets differ from csr_np")
    say(f"{name}: offsets ({len(oracle.offsets)}) and targets ({num_edges}) "
        f"equal csr_np element for element")


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def run_one_chip(scale: int) -> None:
    """Text -> CSR on the default ``device`` engine (cold, then warm),
    then an edgelist-only ``.gvel`` snapshot -> CSR."""
    import jax

    from repro.core import open_graph, parse

    dev = jax.devices()[0]
    say(f"donation supported: {parse.donation_supported()}")
    path, oracle, v, e = make_graph(scale)

    for run in ("cold (includes compiling)", "warm"):
        t0 = time.perf_counter()
        csr = open_graph(path, num_vertices=v).csr()
        jax.block_until_ready((csr.offsets, csr.targets))
        dt = time.perf_counter() - t0
        say(f"smoke timing, not a benchmark: text load_csr {run}: {dt:.3f} s "
            f"({e / dt:.4g} edges/s)")
        check_csr(f"text csr {run.split()[0]}", csr, oracle, e)
    say(f"device peak_bytes_in_use after text loads: {peak_bytes(dev)}")

    snap = str(DATA_DIR / f"graph500_s{scale}.gvel")
    t0 = time.perf_counter()
    open_graph(path, engine="device", num_vertices=v).save(snap, csr=False)
    say(f"setup: edgelist-only snapshot {os.path.getsize(snap)} bytes "
        f"written in {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    csr = open_graph(snap).csr(method="staged")
    jax.block_until_ready((csr.offsets, csr.targets))
    dt = time.perf_counter() - t0
    say(f"smoke timing, not a benchmark: .gvel edgelist load_csr: {dt:.3f} s "
        f"({e / dt:.4g} edges/s)")
    check_csr("gvel csr", csr, oracle, e)
    say(f"device peak_bytes_in_use after all loads: {peak_bytes(dev)}")


def run_mesh(scale: int, chips: int) -> None:
    """Text -> CSR sharded row-wise over a ``chips``-device mesh."""
    import jax
    import numpy as np

    from repro.core import compat, open_graph

    devices = jax.devices()[:chips]
    if len(devices) < chips:
        fail(f"--chips {chips} needs {chips} devices, JAX has "
             f"{len(jax.devices())}")
    mesh = compat.device_mesh(devices, ("data",))
    path, oracle, v, e = make_graph(scale)

    t0 = time.perf_counter()
    csr = open_graph(path, num_vertices=v).csr_sharded(mesh)
    jax.block_until_ready((csr.offsets, csr.targets))
    dt = time.perf_counter() - t0
    say(f"smoke timing, not a benchmark: sharded load_csr on {chips} "
        f"devices, cold (includes compiling): {dt:.3f} s ({e / dt:.4g} "
        f"edges/s)")

    held = {s.device for s in csr.targets.addressable_shards}
    if held != set(devices):
        fail(f"targets shards sit on {sorted(d.id for d in held)}, not on "
             f"all of {[d.id for d in devices]}")
    for d in devices:
        stats = d.memory_stats() or {}
        say(f"device {d.id} bytes_in_use with the sharded CSR live: "
            f"{stats.get('bytes_in_use', 'not reported')}")

    off = np.asarray(csr.offsets)
    tgt = np.asarray(csr.targets)
    rows = off.shape[1] - 1
    if rows * chips < v:
        fail(f"{chips} shards of {rows} rows do not cover {v} vertices")
    for k in range(chips):
        lo = min(k * rows, v)
        hi = min(lo + rows, v)
        e_lo, e_hi = oracle.offsets[lo], oracle.offsets[hi]
        want_off = oracle.offsets[lo:hi + 1] - e_lo
        n_rows = hi - lo
        if not (np.array_equal(off[k, :n_rows + 1], want_off)
                and np.all(off[k, n_rows:] == off[k, n_rows])):
            fail(f"shard {k}: offsets of rows [{lo}, {hi}) differ from "
                 f"csr_np")
        if not np.array_equal(tgt[k, :e_hi - e_lo], oracle.targets[e_lo:e_hi]):
            fail(f"shard {k}: targets of rows [{lo}, {hi}) differ from csr_np")
        say(f"shard {k}: rows [{lo}, {hi}), {e_hi - e_lo} edges, equal "
            f"csr_np element for element")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded load on a 4-chip mesh")
    args = ap.parse_args(argv)

    import jax

    from repro.core import env

    cache_dir = env.use_compilation_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (device 0 is {dev.platform!r}); this smoke "
             f"run does not fall back to another backend")
    say(f"device: {dev.device_kind}, {len(jax.devices())} device(s), "
        f"jax {jax.__version__}")
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache: {cache_dir} ({cached} entries before this run)")
    try:
        if args.chips == 1:
            run_one_chip(SCALE)
        else:
            run_mesh(SCALE, args.chips)
    finally:
        shutil.rmtree(DATA_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
