"""Byte-range-sharded streaming load: shard_plan partitioning, span
block sources, and the end-to-end mesh CSR build under 4 host devices.

The subprocess tests each assert the sharded result against the host
``build.csr_np`` oracle *bitwise* on offsets/targets (span order ==
file order + stable bucketing + sender-major all_to_all + stable local
sort reproduce global file order per row; see
``exchange_by_owner``'s docstring) — not just as edge sets.
"""
import gzip
import json
import os

import numpy as np
import pytest

from repro.core import codecs
from repro.core.blocks import (MemoryBlockSource, SequentialBlockSource,
                               plan_blocks, shard_plan)


# ---------------------------------------------------------------------------
# shard_plan: host-side partition properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nbytes,beta,d", [
    (100_000, 2048, 4), (100_000, 2048, 3), (1_000, 256, 7),
    (50, 4096, 4), (0, 1024, 2), (8192, 1024, 8),
])
def test_shard_plan_partitions_blocks(nbytes, beta, d):
    plan = plan_blocks(nbytes, beta=beta, overlap=64)
    spans = [shard_plan(plan, k, d) for k in range(d)]
    # disjoint, ordered, exhaustive cover of [0, num_blocks)
    assert spans[0].block_lo == 0
    assert spans[-1].block_hi == plan.num_blocks
    for a, b in zip(spans, spans[1:]):
        assert a.block_hi == b.block_lo
    # balanced to within one block
    sizes = [s.num_blocks for s in spans]
    assert max(sizes) - min(sizes) <= 1
    # byte spans clamp to the file and never regress
    for s in spans:
        assert 0 <= s.byte_lo <= s.byte_hi <= plan.file_len
        assert s.edge_cap == s.num_blocks * plan.edge_cap


def test_shard_plan_validates():
    plan = plan_blocks(1000, beta=256, overlap=64)
    with pytest.raises(ValueError):
        shard_plan(plan, 0, 0)
    with pytest.raises(ValueError):
        shard_plan(plan, 2, 2)
    with pytest.raises(ValueError):
        shard_plan(plan, -1, 2)


def _lines(n, seed=0, weighted=False):
    rng = np.random.default_rng(seed)
    src = rng.integers(1, 900, n)
    dst = rng.integers(1, 900, n)
    if weighted:
        w = (rng.random(n) * 9).round(3)
        body = "\n".join(f"{s} {d} {x}" for s, d, x in zip(src, dst, w))
    else:
        body = "\n".join(f"{s} {d}" for s, d in zip(src, dst))
    return (body + "\n").encode()


# ---------------------------------------------------------------------------
# span block sources: staged bytes match the in-memory source, per shard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["raw", "gzip", "framed-zlib"])
@pytest.mark.parametrize("d", [1, 3, 4])
def test_shard_source_staging_parity(tmp_path, fmt, d):
    data = _lines(3000, seed=2)
    raw = tmp_path / "g.el"
    raw.write_bytes(data)
    if fmt == "raw":
        path = str(raw)
    elif fmt == "gzip":
        path = str(tmp_path / "g.el.gz")
        with open(path, "wb") as f:
            f.write(gzip.compress(data))
    else:
        path = str(tmp_path / "g.el.fz")
        codecs.write_framed(path, data, codec="zlib", frame_beta=4096)

    length, forced = codecs.stream_geometry(path)
    assert length == len(data)
    plan = plan_blocks(length, beta=forced or 2048, overlap=64)
    ref = MemoryBlockSource(np.frombuffer(data, np.uint8))
    for k in range(d):
        span = shard_plan(plan, k, d)
        if span.num_blocks == 0:
            with pytest.raises(ValueError):
                codecs.open_shard_block_source(path, plan, span)
            continue
        source = codecs.open_shard_block_source(path, plan, span)
        for lo in range(span.block_lo, span.block_hi, 3):
            ids = np.arange(lo, min(lo + 3, span.block_hi))
            got = source.stage(plan, ids)
            want = ref.stage(plan, ids)
            assert np.array_equal(got, want), (fmt, k, lo)
        source.finish()


@pytest.mark.parametrize("k,d,match", [
    (1, 3, "before this shard span"),   # mid-stream span: coverage check
    (1, 2, "expected"),                 # tail span: exact-drain check
])
def test_span_source_truncated_stream_raises(k, d, match):
    data = b"1 2\n3 4\n5 6\n" * 400
    plan = plan_blocks(len(data), beta=256, overlap=64)
    span = shard_plan(plan, k, d)

    def chunks():
        # begins at the span's left margin but ends short of span.byte_hi
        start = max(span.block_lo * plan.beta - plan.overlap, 0)
        yield data[start:span.byte_hi - 40]

    src = SequentialBlockSource(
        chunks(), len(data),
        start=max(span.block_lo * plan.beta - plan.overlap, 0),
        end=span.byte_hi if span.block_hi < plan.num_blocks else None,
        first_block=span.block_lo)
    with pytest.raises(ValueError, match=match):
        for lo in range(span.block_lo, span.block_hi, 4):
            src.stage(plan, np.arange(lo, min(lo + 4, span.block_hi)))
        src.finish()


def test_span_source_rejects_out_of_order():
    data = b"1 2\n" * 500
    plan = plan_blocks(len(data), beta=256, overlap=64)
    src = SequentialBlockSource(iter([data]), len(data))
    src.stage(plan, np.arange(0, 2))
    with pytest.raises(ValueError, match="out of order"):
        src.stage(plan, np.arange(5, 6))


# ---------------------------------------------------------------------------
# tuner: per-shard-count profile slot
# ---------------------------------------------------------------------------

def test_tuned_shard_slot(tmp_path, monkeypatch):
    from repro.core import loader, tune
    from repro.core.loader import LoadOptions, resolve_tuned

    cache = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache))
    rows = [{"beta": 4096, "batch_blocks": 2, "seconds": 0.5, "mb_per_s": 1.0}]
    tune.save_geometry(rows, weighted=False, shards=4)
    rows1 = [{"beta": 65536, "batch_blocks": 8, "seconds": 0.4,
              "mb_per_s": 1.0}]
    tune.save_geometry(rows1, weighted=False)

    prof = json.loads(cache.read_text())
    slots = prof["hosts"][tune.host_key()]
    assert set(slots) == {"unweighted", "unweighted_d4"}

    opts = LoadOptions(engine="device", weighted=False, tune=True)
    r1 = resolve_tuned(opts)
    assert r1.engine_kw["beta"] == 65536
    r4 = resolve_tuned(opts, shards=4)
    assert r4.engine_kw["beta"] == 4096
    # explicit geometry still wins over the profile
    pinned = opts.replace(engine_kw={"beta": 1024, "batch_blocks": 2})
    assert resolve_tuned(pinned, shards=4).engine_kw["beta"] == 1024


# ---------------------------------------------------------------------------
# front-door guards (no mesh computation needed)
# ---------------------------------------------------------------------------

def test_read_csr_sharded_via_guards(tmp_path):
    from repro.core.compat import make_mesh
    from repro.core.loader import LoadOptions, read_csr_sharded_via

    path = tmp_path / "g.el"
    path.write_bytes(b"1 2\n2 3\n")
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="no axis"):
        read_csr_sharded_via(str(path), LoadOptions(engine="device"),
                             mesh=mesh, axis="model")
    with pytest.raises(ValueError, match="symmetric"):
        read_csr_sharded_via(str(path),
                             LoadOptions(engine="device", symmetric=True),
                             mesh=mesh)
    with pytest.raises(ValueError, match="no sharded streaming path"):
        read_csr_sharded_via(str(path), LoadOptions(engine="numpy"),
                             mesh=mesh)


def test_csr_sharded_front_door_rejects_mtx_and_gvel(tmp_path):
    from repro.core import open_graph, save_snapshot
    from repro.core.compat import make_mesh
    from repro.core.types import EdgeList

    mesh = make_mesh((1,), ("data",))
    mtx = tmp_path / "g.mtx"
    mtx.write_text("%%MatrixMarket matrix coordinate pattern general\n"
                   "3 3 2\n1 2\n2 3\n")
    with pytest.raises(ValueError, match="MTX"):
        open_graph(str(mtx)).csr_sharded(mesh)

    snap = tmp_path / "g.gvel"
    el = EdgeList(np.array([0, 1], np.int32), np.array([1, 2], np.int32),
                  None, np.int64(2), 3)
    save_snapshot(str(snap), edgelist=el)
    with pytest.raises(ValueError, match="snapshot"):
        open_graph(str(snap)).csr_sharded(mesh)


def test_csr_sharded_single_device_memoized(tmp_path):
    """d=1 degenerate mesh: the sharded path reduces to the streaming
    load; memoized per (mesh, axis)."""
    from repro.core import build, open_graph
    from repro.core.compat import make_mesh

    rng = np.random.default_rng(3)
    n, v = 1200, 97
    src = rng.integers(0, v, n)
    dst = rng.integers(0, v, n)
    path = tmp_path / "g.el"
    path.write_text("\n".join(f"{s+1} {d+1}" for s, d in zip(src, dst)) + "\n")

    mesh = make_mesh((1,), ("data",))
    g = open_graph(str(path), engine="device", beta=2048)
    csr = g.csr_sharded(mesh)
    assert g.csr_sharded(mesh) is csr
    assert g.csr_sharded(mesh, axis="data") is csr
    assert list(g._sharded_csrs) == [(mesh, "data")]

    oracle = build.csr_np(src, dst, None, v)
    off = np.asarray(csr.offsets)
    tgt = np.asarray(csr.targets)
    rows = off.shape[1] - 1
    assert rows >= v
    assert np.array_equal(off[0, :v + 1], np.asarray(oracle.offsets))
    assert np.array_equal(tgt[0, :n], np.asarray(oracle.targets))


# ---------------------------------------------------------------------------
# end-to-end sharded load under 4 forced host devices (subprocess)
# ---------------------------------------------------------------------------

_ORACLE_HELPERS = '''
import numpy as np
from repro.core import build

def check_bitwise(csr, src, dst, w, v, d):
    """Sharded CSR == csr_np oracle, bitwise on offsets/targets."""
    oracle = build.csr_np(src, dst, w, v)
    oo = np.asarray(oracle.offsets); ot = np.asarray(oracle.targets)
    off = np.asarray(csr.offsets); tgt = np.asarray(csr.targets)
    ww = np.asarray(csr.weights) if w is not None else None
    rows = off.shape[1] - 1
    assert rows * d >= v, (rows, d, v)
    n = 0
    for k in range(d):
        for r in range(rows):
            u = k * rows + r
            lo, hi = int(off[k, r]), int(off[k, r + 1])
            if u >= v:
                assert lo == hi, (k, r)
                continue
            glo, ghi = int(oo[u]), int(oo[u + 1])
            assert hi - lo == ghi - glo, (u, lo, hi, glo, ghi)
            assert np.array_equal(tgt[k, lo:hi], ot[glo:ghi]), u
            if ww is not None:
                np.testing.assert_allclose(
                    ww[k, lo:hi], np.asarray(oracle.weights)[glo:ghi],
                    rtol=1e-6, atol=1e-7)
            n += hi - lo
    assert n == len(src), (n, len(src))
'''


def test_sharded_parity_matrix(devices4, tmp_path):
    """weighted x base x codec grid vs the csr_np oracle, one subprocess."""
    code = _ORACLE_HELPERS + f"""
import gzip, os
import jax
from repro.core import codecs, open_graph
from repro.core.compat import make_mesh
from repro.core import parse_np

calls = [0]
orig = parse_np.parse_chunk_np
parse_np.parse_chunk_np = lambda *a, **k: (calls.__setitem__(0, calls[0] + 1)
                                           or orig(*a, **k))

mesh = make_mesh((4,), ("data",))
rng = np.random.default_rng(11)
n, v = 4000, 333
src = rng.integers(0, v, n); dst = rng.integers(0, v, n)
w = (rng.random(n) * 9).round(3).astype(np.float32)
tmp = r"{tmp_path}"

for weighted in (False, True):
    for base in (0, 1):
        if weighted:
            body = "\\n".join(f"{{s+base}} {{d+base}} {{x:.3f}}"
                              for s, d, x in zip(src, dst, w))
        else:
            body = "\\n".join(f"{{s+base}} {{d+base}}"
                              for s, d in zip(src, dst))
        raw = os.path.join(tmp, f"g_{{weighted}}_{{base}}.el")
        open(raw, "w").write(body + "\\n")
        data = open(raw, "rb").read()
        gz = raw + ".gz"
        open(gz, "wb").write(gzip.compress(data))
        fz = raw + ".fz"
        codecs.write_framed(fz, data, codec="zlib", frame_beta=4096)
        for path in (raw, gz, fz):
            g = open_graph(path, engine="device", weighted=weighted,
                           base=base, beta=2048)
            csr = g.csr_sharded(mesh)
            check_bitwise(csr, src, dst, w if weighted else None, v, 4)
assert calls[0] == 0, f"host parser ran {{calls[0]}} times on the hot path"
print("PARITY-MATRIX-OK")
"""
    assert "PARITY-MATRIX-OK" in devices4(code)


def test_mesh_wider_than_file(devices4, tmp_path):
    """A 4-shard mesh over a 2-line file: empty spans stay device-resident
    and the CSR still matches the oracle."""
    code = _ORACLE_HELPERS + f"""
from repro.core import open_graph
from repro.core.compat import make_mesh

path = r"{tmp_path}/tiny.el"
open(path, "w").write("1 2\\n2 1\\n")
mesh = make_mesh((4,), ("data",))
csr = open_graph(path, engine="device").csr_sharded(mesh)
src = np.array([0, 1]); dst = np.array([1, 0])
check_bitwise(csr, src, dst, None, 2, 4)
print("TINY-OK")
"""
    assert "TINY-OK" in devices4(code)


def test_indivisible_v_with_zero_edge_shard(devices4, tmp_path):
    """V=13 over d=4 (rows=4: last shard owns only vertex 12) with all
    edges among vertices 0..5 — shards own zero edges / zero vertices'
    worth of real rows and the build still matches."""
    code = _ORACLE_HELPERS + f"""
from repro.core import open_graph
from repro.core.compat import make_mesh

rng = np.random.default_rng(5)
n = 600
src = rng.integers(0, 6, n); dst = rng.integers(0, 6, n)
path = r"{tmp_path}/lop.el"
open(path, "w").write(
    "\\n".join(f"{{s+1}} {{d+1}}" for s, d in zip(src, dst)) + "\\n")
mesh = make_mesh((4,), ("data",))
csr = open_graph(path, engine="device", num_vertices=13,
                 beta=1024).csr_sharded(mesh)
assert csr.num_vertices == 13
check_bitwise(csr, src, dst, None, 13, 4)
print("INDIVISIBLE-OK")
"""
    assert "INDIVISIBLE-OK" in devices4(code)


def test_send_cap_overflow_raises(devices4, tmp_path):
    """A hand-passed send_cap too small for a hub graph raises instead of
    silently dropping edges."""
    code = f"""
import numpy as np
from repro.core.compat import make_mesh
from repro.core.distributed import load_csr_sharded_stream

path = r"{tmp_path}/hub.el"
# every edge targets owner shard 0 (src=1): buckets are maximally skewed
open(path, "w").write("".join("1 {{}}\\n".format(i % 40 + 1)
                              for i in range(400)))
mesh = make_mesh((4,), ("data",))
try:
    load_csr_sharded_stream(mesh, "data", path, num_vertices=40, send_cap=1)
except ValueError as exc:
    assert "overflow" in str(exc), exc
    print("OVERFLOW-OK")
else:
    raise SystemExit("expected ValueError")
"""
    assert "OVERFLOW-OK" in devices4(code)


def test_host_shard_and_load_uses_stream_path(devices4, tmp_path):
    """The compatibility wrapper rides the streamed pipeline: no host
    parser call, same oracle-bitwise result."""
    code = _ORACLE_HELPERS + f"""
from repro.core import host_shard_and_load, parse_np
from repro.core.compat import make_mesh

calls = [0]
orig = parse_np.parse_chunk_np
parse_np.parse_chunk_np = lambda *a, **k: (calls.__setitem__(0, calls[0] + 1)
                                           or orig(*a, **k))
rng = np.random.default_rng(9)
n, v = 2000, 128
src = rng.integers(0, v, n); dst = rng.integers(0, v, n)
path = r"{tmp_path}/c.el"
open(path, "w").write(
    "\\n".join(f"{{s+1}} {{d+1}}" for s, d in zip(src, dst)) + "\\n")
mesh = make_mesh((4,), ("data",))
csr = host_shard_and_load(mesh, "data", path, num_vertices=v)
check_bitwise(csr, src, dst, None, v, 4)
assert calls[0] == 0, calls[0]
print("COMPAT-OK")
"""
    assert "COMPAT-OK" in devices4(code)


def test_graph500_sharded_load_matches_csr_np(devices4, tmp_path):
    """A Graph500 graph (scale 12, 1-based text, about ten blocks a
    shard) through ``open_graph(path, num_vertices=V).csr_sharded(mesh)``
    equals ``csr_np`` of the generator's own edges, offsets and targets
    exactly."""
    code = _ORACLE_HELPERS + f"""
from repro.core import open_graph
from repro.core.compat import make_mesh
from repro.core.generate import rmat_edges, write_edgelist

src, dst, v = rmat_edges(12, 16, seed=22)
path = r"{tmp_path}/g500.el"
write_edgelist(path, src, dst, base=1)
mesh = make_mesh((4,), ("data",))
csr = open_graph(path, num_vertices=v, beta=1 << 14).csr_sharded(mesh)
assert csr.num_vertices == v
check_bitwise(csr, src, dst, None, v, 4)
print("G500-OK")
"""
    assert "G500-OK" in devices4(code)


def test_sharded_load_spans(devices4, tmp_path):
    """In a profiler session the sharded load opens each of its four
    spans once, and ``load.exchange`` carries the exchange's geometry:
    the shard count, ``send_cap`` on the ``_cap_round`` ladder, the
    scanned prefix ``edge_limit`` and the edges parsed (E)."""
    code = f"""
import collections, glob, json
import jax
from repro.core import open_graph
from repro.core.compat import make_mesh
from repro.core.distributed import _cap_round
from repro.core.generate import rmat_edges, write_edgelist

src, dst, v = rmat_edges(10, 16, seed=5)
path = r"{tmp_path}/g.el"
write_edgelist(path, src, dst, base=1)
mesh = make_mesh((4,), ("data",))
load = lambda: open_graph(path, num_vertices=v, beta=1 << 13).csr_sharded(mesh)
load()                                   # compile outside the session
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
jax.profiler.start_trace(r"{tmp_path}/trace", profiler_options=opts)
try:
    jax.block_until_ready(load().targets)
finally:
    jax.profiler.stop_trace()
(f,) = glob.glob(r"{tmp_path}/trace/**/*.xplane.pb", recursive=True)
counts, stats = collections.Counter(), []
for plane in jax.profiler.ProfileData.from_file(f).planes:
    for line in plane.lines:
        for ev in line.events:
            if ev.name.startswith("load."):
                assert plane.name.startswith("/host:"), plane.name
                counts[ev.name] += 1
                if ev.name == "load.exchange":
                    stats.append(dict(ev.stats))
for name in ("load.shard_join", "load.bucket_histogram", "load.exchange",
             "load.assemble"):
    assert counts[name] == 1, (name, counts)
(st,) = stats
assert set(st) == {{"shards", "send_cap", "edge_limit", "edges"}}, st
assert st["shards"] == 4 and st["edges"] == len(src), st
assert _cap_round(st["send_cap"]) == st["send_cap"], st
assert 16 * st["send_cap"] >= st["edges"], st
assert 4 * st["edge_limit"] >= st["edges"], st
print("SPANS-OK", json.dumps(st))
"""
    assert "SPANS-OK" in devices4(code)


def test_max_vertex_id_only_without_num_vertices(tmp_path, monkeypatch):
    """The sharded load reads the largest vertex id off the devices only
    when the caller gave no ``num_vertices``; either way the CSR equals
    the oracle."""
    from repro.core import build, distributed, open_graph
    from repro.core.compat import make_mesh

    calls = []
    real = distributed._max_vertex_id
    monkeypatch.setattr(distributed, "_max_vertex_id",
                        lambda s, d: calls.append(1) or real(s, d))
    rng = np.random.default_rng(4)
    n, v = 1500, 113
    src = rng.integers(0, v, n)
    dst = rng.integers(0, v, n)
    dst[7] = v - 1
    path = tmp_path / "g.el"
    path.write_text("\n".join(f"{s+1} {d+1}" for s, d in zip(src, dst)) + "\n")
    oracle = build.csr_np(src, dst, None, v)
    mesh = make_mesh((1,), ("data",))

    for kw, want_calls in (({"num_vertices": v}, 0), ({}, 1)):
        calls.clear()
        csr = open_graph(str(path), beta=2048, **kw).csr_sharded(mesh)
        assert len(calls) == want_calls, kw
        assert csr.num_vertices == v
        assert np.array_equal(np.asarray(csr.offsets)[0, :v + 1],
                              np.asarray(oracle.offsets))
        assert np.array_equal(np.asarray(csr.targets)[0, :n],
                              np.asarray(oracle.targets))
