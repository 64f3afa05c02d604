"""GraphSource front door: wrapper parity vs ``load_*`` across engines
x codecs, header-only ``info()``, section-selective lazy decompression
(instrumented codec counter), deferred corruption errors, memoization,
``LoadOptions`` normalization, and the ``python -m repro.core.source``
probe."""
import gzip
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (LoadOptions, available_engines, codecs, get_engine,
                        load_csr, load_edgelist, open_graph, read_snapshot,
                        register_engine, save_snapshot, write_framed)
from repro.core.build import csr_np
from repro.core.csr import convert_to_csr
from repro.core.generate import write_edgelist
from repro.core.loader import _REGISTRY
from repro.core.mtx import read_mtx, write_mtx
from repro.core.snapshot import (SEC_CSR_INDICES, SEC_CSR_OFFSETS,
                                 SEC_CSR_WEIGHTS, SEC_DST, SEC_EDGE_WEIGHTS,
                                 SEC_SRC, SnapshotError)

ENGINES = ["device", "numpy", "threads", "pallas"]
# same staging shapes as test_loader.py so jitted programs are shared;
# framed files force beta to their frame size
SMALL_KW = {"device": dict(beta=4096, batch_blocks=2),
            "pallas": dict(beta=2048, batch_blocks=2)}
FRAME_BETA = {"device": 4096, "pallas": 2048, "numpy": 4096, "threads": 4096}
FORMATS = ["raw", "gzip", "framed-zlib"]


def _graph(tmp_path, *, weighted, base, seed=0, v=60, e=400):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, e)
    dst = rng.integers(0, v, e)
    w = (rng.random(e) * 9).round(3).astype(np.float32) if weighted else None
    path = str(tmp_path / f"g_{weighted}_{base}.el")
    write_edgelist(path, src, dst, w, base=base)
    oracle = csr_np(src.astype(np.int32), dst.astype(np.int32), w, v)
    return path, v, e, oracle


def _compressed(path, fmt, frame_beta=4096):
    if fmt == "raw":
        return path
    raw = open(path, "rb").read()
    if fmt == "gzip":
        out = path + ".gz"
        with open(out, "wb") as f:
            f.write(gzip.compress(raw))
        return out
    out = path + ".elz"
    write_framed(out, raw, codec="zlib", frame_beta=frame_beta)
    return out


def _zlib_snapshot(tmp_path, *, weighted=False, seed=3, name="g.z.gvel"):
    """Both-sections (edgelist + prebuilt CSR) zlib-compressed .gvel."""
    path, v, e, oracle = _graph(tmp_path, weighted=weighted, base=1, seed=seed)
    el = load_edgelist(path, engine="numpy", weighted=weighted,
                       num_vertices=v)
    gv = str(tmp_path / name)
    save_snapshot(gv, edgelist=el, csr=convert_to_csr(el, engine="numpy"),
                  compress="zlib")
    return gv, v, e, oracle


def _assert_edgelists_identical(a, b):
    na, nb = int(a.num_edges), int(b.num_edges)
    assert na == nb
    assert a.num_vertices == b.num_vertices
    assert np.array_equal(np.asarray(a.src[:na]), np.asarray(b.src[:nb]))
    assert np.array_equal(np.asarray(a.dst[:na]), np.asarray(b.dst[:nb]))
    if a.weights is None:
        assert b.weights is None
    else:
        assert np.array_equal(np.asarray(a.weights[:na]),
                              np.asarray(b.weights[:nb]))


def _assert_csrs_identical(a, b):
    assert a.num_vertices == b.num_vertices
    assert np.array_equal(np.asarray(a.offsets, np.int64),
                          np.asarray(b.offsets, np.int64))
    assert np.array_equal(np.asarray(a.targets), np.asarray(b.targets))
    if a.weights is None:
        assert b.weights is None
    else:
        assert np.array_equal(np.asarray(a.weights), np.asarray(b.weights))


# ---- wrapper parity: load_* == GraphSource products -------------------------

@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("weighted,base", [(False, 1), (False, 0),
                                           (True, 1), (True, 0)])
def test_wrapper_parity(tmp_path, engine, fmt, weighted, base):
    """load_edgelist/load_csr outputs are element-identical to the
    GraphSource products they now wrap — same engine, same bytes."""
    path, v, e, oracle = _graph(tmp_path, weighted=weighted, base=base,
                                seed=base + 2 * weighted)
    cpath = _compressed(path, fmt, frame_beta=FRAME_BETA[engine])
    kw = SMALL_KW.get(engine, {})

    el_w = load_edgelist(cpath, engine=engine, weighted=weighted, base=base,
                         **kw)
    src = open_graph(cpath, engine=engine, weighted=weighted, base=base, **kw)
    _assert_edgelists_identical(el_w, src.edgelist())

    csr_w = load_csr(cpath, engine=engine, weighted=weighted, base=base,
                     num_vertices=v, **kw)
    src2 = open_graph(cpath, engine=engine, weighted=weighted, base=base,
                      num_vertices=v, **kw)
    _assert_csrs_identical(csr_w, src2.csr())


@pytest.mark.parametrize("compress", [None, "zlib"])
@pytest.mark.parametrize("weighted", [False, True])
def test_wrapper_parity_snapshot_engine(tmp_path, compress, weighted):
    path, v, e, _ = _graph(tmp_path, weighted=weighted, base=1, seed=5)
    el = load_edgelist(path, engine="numpy", weighted=weighted,
                       num_vertices=v)
    gv = str(tmp_path / "g.gvel")
    save_snapshot(gv, edgelist=el, csr=convert_to_csr(el, engine="numpy"),
                  compress=compress)
    _assert_edgelists_identical(
        load_edgelist(gv, weighted=weighted),
        open_graph(gv, weighted=weighted).edgelist())
    _assert_csrs_identical(
        load_csr(gv, weighted=weighted),
        open_graph(gv, weighted=weighted).csr())


# ---- laziness: header-only info(), section-selective decode -----------------

def test_info_reads_header_only_despite_corrupt_payload(tmp_path):
    """Corrupt a byte inside the first (edgelist src) section payload:
    info() — header + table only — must not notice; the eager reader
    and the first .edgelist() access must."""
    gv, v, e, oracle = _zlib_snapshot(tmp_path)
    with open(gv, "r+b") as f:
        f.seek(4096 + 30)              # inside section 1's frame stream
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0x20]))
    get_engine("snapshot").clear_memo()

    src = open_graph(gv)               # validate=True: headers are fine
    info = src.info()
    assert info.format == "gvel" and info.version == 2
    assert info.num_vertices == v and info.num_edges == e
    assert info.codec == "zlib"
    assert info.has_edgelist and info.has_csr

    with pytest.raises(SnapshotError):         # deferred to first access
        src.edgelist()
    # ... but the CSR sections are intact, and only they decode:
    _assert_csrs_identical(src.csr(), oracle)
    with pytest.raises(SnapshotError):         # eager reader: fails at open
        read_snapshot(gv)


def test_inconsistent_csr_offsets_stay_fatal_on_retry(tmp_path):
    """Offsets whose tail disagrees with the header raise at first
    decode AND on every retry — the lazily-memoized cell must not
    serve the inconsistent array the second time around."""
    path, v, e, oracle = _graph(tmp_path, weighted=False, base=1, seed=11)
    el = load_edgelist(path, engine="numpy", num_vertices=v)
    bad_off = np.asarray(oracle.offsets).copy()
    bad_off[-1] -= 1                    # lengths stay right, tail lies
    from repro.core import CSR
    gv = str(tmp_path / "bad_off.z.gvel")
    save_snapshot(gv, edgelist=el,
                  csr=CSR(bad_off, oracle.targets, None, v),
                  compress="zlib")
    get_engine("snapshot").clear_memo()
    src = open_graph(gv)
    with pytest.raises(SnapshotError, match="offsets end"):
        src.csr()
    with pytest.raises(SnapshotError, match="offsets end"):
        src.csr()                       # retry must not serve bad data
    with pytest.raises(SnapshotError, match="offsets end"):
        open_graph(gv).csr()            # nor a fresh handle via the memo


def _decoded_sids(calls):
    return {int(c.rsplit(" ", 1)[1]) for c in calls}


@pytest.mark.parametrize("weighted", [False, True])
def test_csr_never_decodes_edgelist_frames(tmp_path, monkeypatch, weighted):
    """Instrumented codec counter: cold .csr() on a both-sections
    compressed snapshot decodes only CSR sections — never the edgelist
    frame streams, and not even CSR weights unless asked for."""
    gv, v, e, oracle = _zlib_snapshot(tmp_path, weighted=weighted)
    calls = []
    orig = codecs.decompress_frames

    def spy(payload, raw_len, codec, *, context="frame stream"):
        calls.append(context)
        return orig(payload, raw_len, codec, context=context)

    monkeypatch.setattr(codecs, "decompress_frames", spy)
    get_engine("snapshot").clear_memo()

    src = open_graph(gv)
    src.info()
    assert calls == []                         # info() decodes nothing
    csr = src.csr()
    assert _decoded_sids(calls) == ({SEC_CSR_OFFSETS, SEC_CSR_INDICES,
                                     SEC_CSR_WEIGHTS} if weighted else
                                    {SEC_CSR_OFFSETS, SEC_CSR_INDICES})
    assert np.array_equal(np.asarray(csr.offsets, np.int64),
                          np.asarray(oracle.offsets))
    calls.clear()
    src.edgelist()                             # now the edgelist decodes
    assert _decoded_sids(calls) == ({SEC_SRC, SEC_DST, SEC_EDGE_WEIGHTS}
                                    if weighted else {SEC_SRC, SEC_DST})


def test_unweighted_load_of_weighted_snapshot_skips_weight_sections(
        tmp_path, monkeypatch):
    gv, v, e, _ = _zlib_snapshot(tmp_path, weighted=True)
    calls = []
    orig = codecs.decompress_frames

    def spy(payload, raw_len, codec, *, context="frame stream"):
        calls.append(context)
        return orig(payload, raw_len, codec, context=context)

    monkeypatch.setattr(codecs, "decompress_frames", spy)
    get_engine("snapshot").clear_memo()
    csr = open_graph(gv, weighted=False).csr()
    assert csr.weights is None
    assert _decoded_sids(calls) == {SEC_CSR_OFFSETS, SEC_CSR_INDICES}


def test_info_on_text_never_parses(tmp_path, monkeypatch):
    path, v, e, _ = _graph(tmp_path, weighted=False, base=1, seed=9)
    monkeypatch.setattr("repro.core.source.read_edgelist_via",
                        lambda *a, **k: pytest.fail("info() parsed the file"))
    monkeypatch.setattr("repro.core.source.read_csr_via",
                        lambda *a, **k: pytest.fail("info() parsed the file"))
    info = open_graph(path).info()
    assert info.format == "text" and info.codec is None
    assert info.num_vertices is None and info.num_edges is None
    assert info.size_bytes == os.path.getsize(path)


def test_info_compressed_text(tmp_path):
    path, v, e, _ = _graph(tmp_path, weighted=False, base=1, seed=9)
    fz = _compressed(path, "framed-zlib")
    info = open_graph(fz).info()
    assert info.format == "text" and info.codec == "framed-zlib"
    assert info.raw_bytes == os.path.getsize(path)
    gz = _compressed(path, "gzip")
    info = open_graph(gz).info()
    assert info.codec == "gzip"
    assert info.raw_bytes == os.path.getsize(path)   # trailer ISIZE


# ---- memoization -------------------------------------------------------------

def test_products_memoized(tmp_path):
    path, v, e, _ = _graph(tmp_path, weighted=False, base=1, seed=2)
    src = open_graph(path, num_vertices=v)
    assert src.edgelist() is src.edgelist()
    assert src.csr() is src.csr()
    assert src._csr is src.csr()             # one whole-graph CSR memo
    assert src.info() is src.info()


@pytest.mark.parametrize("call", [
    lambda p: open_graph(p, method="staged").csr(),
    lambda p: open_graph(p).csr(method="staged"),
    lambda p: open_graph(p).csr(rho=8),
], ids=["open_graph_method", "csr_method", "csr_rho"])
def test_build_options_rejected(tmp_path, call):
    """The CSR build is not selectable: a build option raises instead of
    being forwarded to an engine or building silently."""
    path, v, e, _ = _graph(tmp_path, weighted=False, base=1, seed=2)
    with pytest.raises(TypeError):
        call(path)


def test_csr_fallback_reuses_memoized_edgelist(tmp_path, monkeypatch):
    """With one engine pinned at open, the symmetric CSR route feeds on
    the memoized edgelist instead of re-reading the file."""
    path, v, e, _ = _graph(tmp_path, weighted=False, base=1, seed=2)
    src = open_graph(path, engine="numpy", symmetric=True, num_vertices=v)
    el = src.edgelist()
    for target in ("repro.core.loader.read_edgelist_via",
                   "repro.core.source.read_edgelist_via"):
        monkeypatch.setattr(
            target,
            lambda *a, **k: pytest.fail("re-read despite memoized edgelist"))
    csr = src.csr()
    assert int(csr.offsets[-1]) == 2 * e and int(el.num_edges) == 2 * e


# ---- MTX through the front door ---------------------------------------------

def test_mtx_front_door(tmp_path):
    rng = np.random.default_rng(7)
    v, e = 40, 200
    s, d = rng.integers(0, v, e), rng.integers(0, v, e)
    w = (rng.random(e) * 5).round(2).astype(np.float32)
    m = str(tmp_path / "m.mtx")
    write_mtx(m, s, d, w, num_vertices=v)
    src = open_graph(m)
    info = src.info()
    assert info.format == "mtx" and info.num_vertices == v
    assert info.num_edges == e and info.weighted and info.symmetric is False
    _assert_edgelists_identical(src.edgelist(), read_mtx(m))
    # explicit weighted=False drops the banner's weights
    el = open_graph(m, weighted=False).edgelist()
    assert el.weights is None
    # weighted load of a pattern file is an error
    p = str(tmp_path / "p.mtx")
    write_mtx(p, s, d, num_vertices=v)
    with pytest.raises(ValueError, match="pattern"):
        open_graph(p, weighted=True).edgelist()
    # num_vertices conflicting with the size line is an error
    with pytest.raises(ValueError, match="num_vertices"):
        open_graph(m, num_vertices=v + 5).edgelist()


# ---- stream ------------------------------------------------------------------

def test_stream_product(tmp_path):
    path, v, e, _ = _graph(tmp_path, weighted=False, base=1, seed=4)
    (s, d, w, total), cap = open_graph(
        path, engine="device", **SMALL_KW["device"]).stream()
    assert int(total) == e and w is None and cap >= e
    with pytest.raises(ValueError, match="stream"):
        open_graph(path, engine="numpy").stream()


# ---- save (the symmetric write path) ----------------------------------------

def test_save_roundtrip(tmp_path):
    path, v, e, oracle = _graph(tmp_path, weighted=True, base=1, seed=6)
    src = open_graph(path, engine="numpy", weighted=True, num_vertices=v)
    out = src.save(str(tmp_path / "g.z.gvel"), compress="zlib:9")
    assert out.format == "gvel" and out.info().version == 2
    assert out.info().codec == "zlib"
    _assert_csrs_identical(out.csr(), src.csr())
    # codec spec with level must round-trip losslessly
    _assert_edgelists_identical(out.edgelist(), src.edgelist())


def test_save_csr_only_snapshot(tmp_path):
    path, v, e, oracle = _graph(tmp_path, weighted=False, base=1, seed=6)
    gv = str(tmp_path / "csr_only.gvel")
    save_snapshot(gv, csr=oracle)
    out = open_graph(gv).save(str(tmp_path / "csr_only.z.gvel"),
                              compress="zlib")
    assert out.info().has_csr and not out.info().has_edgelist
    _assert_csrs_identical(out.csr(), oracle)
    # csr=False is unsatisfiable for a CSR-only source: error, not a
    # silently-contradictory output file
    with pytest.raises(SnapshotError, match="csr=False"):
        open_graph(gv).save(str(tmp_path / "nope.gvel"), csr=False)


def test_save_parses_text_input_once(tmp_path, monkeypatch):
    """save() needs both products; a cold text source with no engine
    pinned must not parse the file twice (edgelist read + CSR stream)."""
    import repro.core.source as source_mod
    path, v, e, oracle = _graph(tmp_path, weighted=False, base=1, seed=12)
    reads = []
    orig = source_mod.read_edgelist_via

    def spy(p, opts):
        reads.append(opts.engine)
        return orig(p, opts)

    monkeypatch.setattr(source_mod, "read_edgelist_via", spy)
    monkeypatch.setattr(
        "repro.core.loader.read_edgelist_via",
        lambda *a, **k: pytest.fail("CSR route re-read the file"))
    src = open_graph(path, num_vertices=v)
    out = src.save(str(tmp_path / "once.gvel"))
    assert reads == ["numpy"]          # exactly one parse
    _assert_csrs_identical(out.csr(), src.csr())
    assert np.array_equal(np.asarray(out.csr().offsets, np.int64),
                          np.asarray(oracle.offsets))


def test_unknown_codec_id_rejected_at_open(tmp_path):
    import struct
    gv, v, e, _ = _zlib_snapshot(tmp_path, name="badcodec.z.gvel")
    with open(gv, "r+b") as f:
        f.seek(40 + 24)                # first v2 entry's codec_id field
        f.write(struct.pack("<I", 250))
    get_engine("snapshot").clear_memo()
    with pytest.raises(SnapshotError, match="unknown codec id 250"):
        open_graph(gv)                 # validate=True: table metadata
    # validate=False defers; info() still reports the unknown id
    assert "id250" in open_graph(gv, validate=False).info().codec


# ---- open-time validation ----------------------------------------------------

def test_validate_at_open(tmp_path):
    with pytest.raises(OSError):
        open_graph(str(tmp_path / "missing.el"))
    # validate=False defers existence to first access
    src = open_graph(str(tmp_path / "missing.el"), validate=False)
    with pytest.raises(OSError):
        src.edgelist()
    with pytest.raises(ValueError, match="unknown loader engine"):
        open_graph(str(tmp_path / "missing.el"), engine="no-such",
                   validate=False).edgelist()


def test_externally_compressed_gvel_rejected_at_open(tmp_path):
    path, v, e, _ = _graph(tmp_path, weighted=False, base=1, seed=1)
    el = load_edgelist(path, engine="numpy", num_vertices=v)
    gv = str(tmp_path / "g.gvel")
    save_snapshot(gv, edgelist=el)
    gz = gv + ".gz"
    with open(gz, "wb") as f:
        f.write(gzip.compress(open(gv, "rb").read()))
    with pytest.raises(ValueError, match="compressed .gvel"):
        open_graph(gz)


def test_load_options_normalization():
    with pytest.raises(ValueError, match="base"):
        LoadOptions(base=2)
    with pytest.raises(ValueError, match="offset"):
        LoadOptions(offset=-1)
    with pytest.raises(ValueError, match="engine_kw"):
        LoadOptions(engine_kw={"base": 0})
    opts = LoadOptions(engine="numpy", weighted=True,
                       engine_kw={"chunk_bytes": 1024})
    assert opts.read_kwargs() == dict(chunk_bytes=1024, weighted=True,
                                      base=1, num_vertices=None, offset=0)
    assert "num_vertices" not in opts.stream_kwargs()


# ---- engine registry listing (satellite bugfix regression) ------------------

def test_available_engines_sorted_regardless_of_registration_order():
    class First:
        name = "aaa-test-engine"     # sorts first, registered last

        def read_edgelist(self, path, **kw):
            raise NotImplementedError

    try:
        register_engine(First())
        names = available_engines()
        assert names == sorted(names)
        assert names[0] == "aaa-test-engine"
    finally:
        _REGISTRY.pop("aaa-test-engine", None)


def test_get_engine_unknown_error_lists_sorted_names():
    with pytest.raises(ValueError) as ei:
        get_engine("no-such-engine")
    assert str(available_engines()) in str(ei.value)


# ---- python -m repro.core.source probe ---------------------------------------

def test_module_probe_json(tmp_path):
    gv, v, e, _ = _zlib_snapshot(tmp_path)
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(root, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-m", "repro.core.source", gv],
                         capture_output=True, text=True, env=env, cwd=root)
    assert out.returncode == 0, out.stderr
    info = json.loads(out.stdout)
    assert info["format"] == "gvel" and info["codec"] == "zlib"
    assert info["num_vertices"] == v and info["num_edges"] == e
    bad = subprocess.run([sys.executable, "-m", "repro.core.source",
                          str(tmp_path / "nope.el")],
                         capture_output=True, text=True, env=env, cwd=root)
    assert bad.returncode == 1
    assert "error" in json.loads(bad.stdout)
