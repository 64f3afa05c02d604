"""The fused/donated streaming accumulator, the overlong-line guard, the
block-geometry autotuner, and the bench_diff perf gate.

The load-bearing suite here is the bitwise parity matrix: the fused
``parse_accumulate`` path (one jitted program per batch, donated
accumulators, trimmed tail batch) must produce **element-identical**
CSR outputs to the pre-change two-step pipeline (``parse_blocks`` +
``_accumulate_batch`` with a padded tail) across weighted x base x
codec (raw / gzip / framed-zlib).
"""
import gzip
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import load_csr, open_graph
from repro.core.blocks import owned_range, plan_blocks, stage_blocks, NEWLINE
from repro.core.build import csr_np, csr_staged
from repro.core.codecs import write_framed
from repro.core.generate import write_edgelist
from repro.core.loader import LoadOptions, _accumulate_batch, resolve_tuned
from repro.core.parse import parse_accumulate, parse_blocks
from repro.core.types import CSR
from repro.core import parse as parse_mod
from repro.core import tune as tune_mod

I32 = jnp.int32
SCRIPTS = os.path.join(os.path.dirname(__file__), "..", "scripts")


def _graph(tmp_path, *, weighted, base, seed=0, v=60, e=400):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, e)
    dst = rng.integers(0, v, e)
    # <= 4 significant digits: exact in float32 under either summation
    # order, so the bitwise comparison below is meaningful
    w = (rng.random(e) * 9).round(3).astype(np.float32) if weighted else None
    path = str(tmp_path / f"g_{weighted}_{base}.el")
    write_edgelist(path, src, dst, w, base=base)
    oracle = csr_np(src.astype(np.int32), dst.astype(np.int32), w, v)
    return path, v, e, oracle


def _unfused_pipeline_csr(data: np.ndarray, v: int, *, weighted, base,
                          beta, overlap, batch_blocks) -> CSR:
    """The pre-change streaming engine, reproduced: separately-jitted
    ``parse_blocks`` per padded batch + scatter ``_accumulate_batch``
    (donation off), then the same pow-2 shrink + staged build the
    loader has always used."""
    plan = plan_blocks(len(data), beta=beta, overlap=overlap)
    os_, oe = owned_range(plan)
    ec = plan.edge_cap
    cap = plan.num_blocks * ec
    acc_src = jnp.full((cap,), -1, I32)
    acc_dst = jnp.full((cap,), -1, I32)
    acc_w = jnp.zeros((cap,), jnp.float32) if weighted else None
    total = jnp.zeros((), I32)
    ostart = jnp.full((batch_blocks,), os_, I32)
    oend = jnp.full((batch_blocks,), oe, I32)
    for start in range(0, plan.num_blocks, batch_blocks):
        ids = np.arange(start, min(start + batch_blocks, plan.num_blocks))
        bufs = stage_blocks(data, plan, ids)
        if len(ids) < batch_blocks:       # the old padded tail batch
            pad = np.full((batch_blocks - len(ids), plan.buf_len), NEWLINE,
                          np.uint8)
            bufs = np.concatenate([bufs, pad])
        src_b, dst_b, w_b, counts = parse_blocks(
            jnp.asarray(bufs), ostart, oend, weighted=weighted, base=base,
            edge_cap=ec)
        acc_src, acc_dst, acc_w, total = _accumulate_batch(
            acc_src, acc_dst, acc_w, total, src_b, dst_b, w_b, counts,
            cap=cap, donate=False)
    n = int(total)
    cap2 = 1 << max(n - 1, 1).bit_length()
    if cap2 < acc_src.shape[0]:
        acc_src, acc_dst = acc_src[:cap2], acc_dst[:cap2]
        acc_w = acc_w[:cap2] if weighted else None
    offsets, targets, ww = csr_staged(acc_src, acc_dst, acc_w, v, rho=4,
                                      weighted=weighted)
    return CSR(np.asarray(offsets).astype(np.int64), np.asarray(targets[:n]),
               np.asarray(ww[:n]) if weighted else None, v)


# ---------------------------------------------------------------------------
# bitwise parity: fused engine == pre-change pipeline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", ["raw", "gzip", "framed-zlib"])
@pytest.mark.parametrize("weighted,base", [(False, 1), (False, 0),
                                           (True, 1), (True, 0)])
def test_fused_engine_bitwise_equals_unfused(tmp_path, codec, weighted, base):
    beta, bb, overlap = 2048, 2, 64
    path, v, e, _ = _graph(tmp_path, weighted=weighted, base=base,
                           seed=base + 2 * weighted, e=700)
    raw = np.fromfile(path, np.uint8)
    ref = _unfused_pipeline_csr(raw, v, weighted=weighted, base=base,
                                beta=beta, overlap=overlap, batch_blocks=bb)
    if codec == "gzip":
        load_path = path + ".gz"
        with open(load_path, "wb") as f:
            f.write(gzip.compress(raw.tobytes(), 6))
    elif codec == "framed-zlib":
        load_path = path + ".elz"
        # frame size == beta so the forced plan matches the reference
        write_framed(load_path, raw.tobytes(), codec="zlib", frame_beta=beta)
    else:
        load_path = path
    got = load_csr(load_path, engine="device", weighted=weighted, base=base,
                   num_vertices=v, beta=beta, batch_blocks=bb)
    assert np.array_equal(got.offsets, ref.offsets)
    assert np.array_equal(got.targets, ref.targets)
    if weighted:
        assert np.array_equal(got.weights, ref.weights)
    else:
        assert got.weights is None and ref.weights is None


@pytest.mark.parametrize("weighted,base", [(False, 1), (True, 0)])
def test_pallas_engine_bitwise_equals_device(tmp_path, weighted, base):
    """Both streaming engines run the same fused-donated accumulate off
    the same per-byte values; their CSR outputs must be identical."""
    path, v, e, _ = _graph(tmp_path, weighted=weighted, base=base, seed=21,
                           e=900)
    dev = load_csr(path, engine="device", weighted=weighted, base=base,
                   num_vertices=v, beta=2048, batch_blocks=2)
    pal = load_csr(path, engine="pallas", weighted=weighted, base=base,
                   num_vertices=v, beta=2048, batch_blocks=2)
    assert np.array_equal(dev.offsets, pal.offsets)
    assert np.array_equal(dev.targets, pal.targets)
    if weighted:
        assert np.array_equal(dev.weights, pal.weights)


@pytest.mark.parametrize("beta,bb", [(1024, 2), (2048, 3), (4096, 8),
                                     (16384, 2)])
def test_multi_batch_grid_matches_oracle(tmp_path, beta, bb):
    """beta x batch_blocks grid (every combo exercises a remainder tail
    or a single short batch) against the host oracle."""
    path, v, e, oracle = _graph(tmp_path, weighted=True, base=1, seed=9,
                                e=900)
    csr = load_csr(path, engine="device", weighted=True, num_vertices=v,
                   beta=beta, batch_blocks=bb)
    assert np.array_equal(np.asarray(csr.offsets, np.int64), oracle.offsets)
    off = oracle.offsets
    for u in range(v):
        mine = sorted(zip(np.asarray(csr.targets[off[u]:off[u + 1]]).tolist(),
                          np.asarray(csr.weights[off[u]:off[u + 1]]).tolist()))
        ref = sorted(zip(oracle.targets[off[u]:off[u + 1]].tolist(),
                         oracle.weights[off[u]:off[u + 1]].tolist()))
        assert mine == ref, (beta, bb, u)


def test_tail_remainder_not_padded(tmp_path):
    """5 blocks / batch_blocks=4 -> the tail runs a 1-block program;
    edges and totals still exact."""
    path, v, e, oracle = _graph(tmp_path, weighted=False, base=1, seed=3,
                                e=1200)
    size = os.path.getsize(path)
    beta = -(-size // 5)             # exactly 5 blocks
    csr = load_csr(path, engine="device", num_vertices=v, beta=beta,
                   batch_blocks=4)
    assert np.array_equal(np.asarray(csr.offsets, np.int64), oracle.offsets)
    assert int(csr.offsets[-1]) == e


# ---------------------------------------------------------------------------
# donation: in-place accumulation and its documented fallback
# ---------------------------------------------------------------------------

def _tiny_batch(text=b"1 2\n3 4\n"):
    buf = np.frombuffer(text, np.uint8)
    pad = np.concatenate([buf, np.full((-len(buf)) % 64, NEWLINE, np.uint8)])
    bufs = jnp.asarray(pad[None, :])
    os_ = jnp.zeros((1,), I32)
    oe = jnp.full((1,), bufs.shape[1], I32)
    return bufs, os_, oe


def test_parse_accumulate_donate_and_fallback_agree():
    bufs, os_, oe = _tiny_batch()
    outs = {}
    for donate in (False, True):
        acc_s = jnp.full((8,), -1, I32)
        acc_d = jnp.full((8,), -1, I32)
        tot = jnp.zeros((), I32)
        outs[donate] = parse_accumulate(
            acc_s, acc_d, None, tot, bufs, os_, oe, weighted=False, base=1,
            edge_bound=8, donate=donate)
    for a, b in zip(outs[False], outs[True]):
        if a is not None:
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_donation_consumes_inputs_when_supported():
    if not parse_mod.donation_supported():
        pytest.skip("backend refuses donation; fallback covered elsewhere")
    bufs, os_, oe = _tiny_batch()
    acc_s = jnp.full((8,), -1, I32)
    acc_d = jnp.full((8,), -1, I32)
    out = parse_accumulate(acc_s, acc_d, None, jnp.zeros((), I32), bufs,
                           os_, oe, weighted=False, base=1, edge_bound=8,
                           donate=True)
    out[0].block_until_ready()
    assert acc_s.is_deleted() and acc_d.is_deleted()


def test_loader_parity_when_donation_refused(tmp_path, monkeypatch):
    """The documented fallback: a backend that refuses donation runs the
    same fused program without donate_argnums and loads identically."""
    path, v, e, oracle = _graph(tmp_path, weighted=True, base=1, seed=5)
    with_donation = load_csr(path, engine="device", weighted=True,
                             num_vertices=v, beta=2048, batch_blocks=2)
    monkeypatch.setattr(parse_mod, "donation_supported", lambda: False)
    without = load_csr(path, engine="device", weighted=True, num_vertices=v,
                       beta=2048, batch_blocks=2)
    assert np.array_equal(with_donation.offsets, without.offsets)
    assert np.array_equal(with_donation.targets, without.targets)
    assert np.array_equal(with_donation.weights, without.weights)


# ---------------------------------------------------------------------------
# overlong-line detection
# ---------------------------------------------------------------------------

def _comment_file(tmp_path):
    """8 edge lines (32 bytes), one 100-byte comment line, 30 more edges.

    The comment's content occupies bytes [32, 130] (newline at 131), so
    with ``beta=128`` block 1's left-context window [64, 128) holds no
    newline — the deterministic boundary-crossing violation.
    """
    path = str(tmp_path / "comment.el")
    with open(path, "w") as f:
        f.write("1 2\n" * 8)
        f.write("%" + "c" * 98 + "\n")          # 100 bytes incl newline
        f.write("".join(f"{i} {i + 1}\n" for i in range(50, 80)))
    return path


def test_overlong_comment_crossing_boundary_raises(tmp_path):
    path = _comment_file(tmp_path)
    with pytest.raises(ValueError, match="byte offset 128"):
        load_csr(path, engine="device", beta=128, overlap=64,
                 batch_blocks=2)


def test_overlong_comment_inside_one_block_is_fine(tmp_path):
    path = _comment_file(tmp_path)
    csr = load_csr(path, engine="device", beta=1 << 20, overlap=64)
    assert int(csr.offsets[-1]) == 8 + 30       # comment skipped, edges kept


def test_overlong_detection_through_gzip(tmp_path):
    path = _comment_file(tmp_path)
    gz = path + ".gz"
    with open(path, "rb") as fin, open(gz, "wb") as fout:
        fout.write(gzip.compress(fin.read(), 6))
    with pytest.raises(ValueError, match="overlap=64"):
        load_csr(gz, engine="device", beta=128, overlap=64, batch_blocks=2)


def test_stage_blocks_check_lines_names_offset():
    data = np.frombuffer(b"1 2\n" + b"x" * 300 + b"\n3 4\n", np.uint8)
    plan = plan_blocks(len(data), beta=128, overlap=64)
    with pytest.raises(ValueError, match=r"byte offset 128"):
        stage_blocks(data, plan, np.arange(plan.num_blocks),
                     check_lines=True)
    # without the flag (raw byte staging) the same call stages silently
    stage_blocks(data, plan, np.arange(plan.num_blocks))


def test_in_contract_lines_never_flagged(tmp_path):
    """Lines up to overlap bytes never trigger the check, any geometry."""
    path, v, e, oracle = _graph(tmp_path, weighted=False, base=1, seed=12)
    for beta in (256, 1024, 4096):
        csr = load_csr(path, engine="device", num_vertices=v, beta=beta,
                       overlap=64, batch_blocks=3)
        assert np.array_equal(np.asarray(csr.offsets, np.int64),
                              oracle.offsets)


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------

def _seed_profile(tmp_path, monkeypatch, beta=4096, batch_blocks=3):
    cache = str(tmp_path / "tune.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", cache)
    prof = {"version": tune_mod.PROFILE_VERSION, "hosts": {
        tune_mod.host_key(): {
            "unweighted": {"beta": beta, "batch_blocks": batch_blocks,
                           "sweep": []},
            "weighted": {"beta": beta * 2, "batch_blocks": batch_blocks,
                         "sweep": []}}}}
    with open(cache, "w") as f:
        json.dump(prof, f)
    return cache


def test_tuned_geometry_hits_cache_without_sweeping(tmp_path, monkeypatch):
    _seed_profile(tmp_path, monkeypatch)
    monkeypatch.setattr(tune_mod, "run_sweep",
                        lambda *a, **k: pytest.fail("sweep ran on cache hit"))
    assert tune_mod.tuned_geometry(weighted=False) == {
        "beta": 4096, "batch_blocks": 3}
    assert tune_mod.tuned_geometry(weighted=True) == {
        "beta": 8192, "batch_blocks": 3}


def test_tuned_geometry_sweeps_and_persists_on_miss(tmp_path, monkeypatch):
    cache = str(tmp_path / "fresh.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", cache)
    rows = [{"beta": 1024, "batch_blocks": 2, "seconds": 0.5,
             "mb_per_s": 1.0},
            {"beta": 2048, "batch_blocks": 4, "seconds": 0.9,
             "mb_per_s": 0.5}]
    monkeypatch.setattr(tune_mod, "run_sweep", lambda *a, **k: list(rows))
    got = tune_mod.tuned_geometry(weighted=False)
    assert got == {"beta": 1024, "batch_blocks": 2}
    saved = json.load(open(cache))
    entry = saved["hosts"][tune_mod.host_key()]["unweighted"]
    assert entry["beta"] == 1024 and entry["sweep"] == rows
    # second call must read the file, not re-sweep
    monkeypatch.setattr(tune_mod, "run_sweep",
                        lambda *a, **k: pytest.fail("re-swept"))
    assert tune_mod.tuned_geometry(weighted=False) == got
    assert tune_mod.clear_cache() is True
    assert not os.path.exists(cache)


def test_run_sweep_measures_real_grid():
    data = tune_mod.synthetic_sample(48 * 1024)
    rows = tune_mod.run_sweep(data, betas=(4096, 16384), batch_blocks=(2,),
                              repeat=1)
    assert len(rows) == 2
    assert rows == sorted(rows, key=lambda r: r["seconds"])
    assert all(r["seconds"] > 0 for r in rows)
    best = tune_mod.best_geometry(rows)
    assert best["beta"] in (4096, 16384)


def test_resolve_tuned_fills_unpinned_geometry(tmp_path, monkeypatch):
    _seed_profile(tmp_path, monkeypatch)
    opts = LoadOptions(engine="device", tune=True)
    kw = resolve_tuned(opts).engine_kw
    assert kw == {"beta": 4096, "batch_blocks": 3}
    # explicit values win; only the missing knob is filled
    opts = LoadOptions(engine="device", tune=True,
                       engine_kw={"beta": 777216})
    kw = resolve_tuned(opts).engine_kw
    assert kw == {"beta": 777216, "batch_blocks": 3}
    # host engines ignore tuning entirely
    opts = LoadOptions(engine="numpy", tune=True)
    assert resolve_tuned(opts).engine_kw == {}


def test_load_csr_tune_end_to_end(tmp_path, monkeypatch):
    _seed_profile(tmp_path, monkeypatch, beta=2048, batch_blocks=2)
    path, v, e, oracle = _graph(tmp_path, weighted=False, base=1, seed=8)
    csr = load_csr(path, engine="device", num_vertices=v, tune=True)
    assert np.array_equal(np.asarray(csr.offsets, np.int64), oracle.offsets)
    src = open_graph(path, engine="device", num_vertices=v, tune=True)
    assert np.array_equal(np.asarray(src.csr().offsets, np.int64),
                          oracle.offsets)


# ---------------------------------------------------------------------------
# bench_diff perf gate
# ---------------------------------------------------------------------------

def _rows(**speedups):
    return [{"name": k, "seconds": 1.0, "mb": 1.0, "speedup": v}
            for k, v in speedups.items()]


def _bench_diff(tmp_path, base_rows, cur_rows, *extra):
    b = tmp_path / "base.json"
    c = tmp_path / "cur.json"
    b.write_text(json.dumps(base_rows))
    c.write_text(json.dumps(cur_rows))
    return subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, "bench_diff.py"),
         str(b), str(c), *extra], capture_output=True, text=True)


def test_bench_diff_passes_within_tolerance(tmp_path):
    r = _bench_diff(tmp_path, _rows(a=2.0, b=10.0), _rows(a=1.8, b=9.0))
    assert r.returncode == 0, r.stdout + r.stderr


def test_bench_diff_fails_on_regression(tmp_path):
    r = _bench_diff(tmp_path, _rows(a=2.0), _rows(a=1.0), "--tol", "0.25")
    assert r.returncode == 1
    assert "REGRESSION" in r.stdout + r.stderr


def test_bench_diff_require_floor(tmp_path):
    ok = _bench_diff(tmp_path, _rows(s=5.0), _rows(s=1.3),
                     "--require-only", "--require", "s>=1.0")
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = _bench_diff(tmp_path, _rows(s=5.0), _rows(s=0.9),
                      "--require-only", "--require", "s>=1.0")
    assert bad.returncode == 1
    missing = _bench_diff(tmp_path, _rows(s=5.0), _rows(other=9.9),
                          "--require-only", "--require", "s>=1.0")
    assert missing.returncode == 1
