"""Quickstart: load a graph into EdgeList and CSR with GVEL.

  PYTHONPATH=src python examples/quickstart.py

Everything goes through the GraphSource front door — ``open_graph``
returns a lazy handle that resolves format/codec/engine once, probes
metadata for free (``info()``), and memoizes its products.
"""
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import available_engines, make_graph_file, open_graph


def main():
    tmp = tempfile.mkdtemp()
    path = os.path.join(tmp, "web.el")
    print("generating an RMAT web-like graph ...")
    v, e = make_graph_file(path, "rmat", scale=14, edge_factor=16)
    size = os.path.getsize(path)
    print(f"  |V|={v:,} |E|={e:,}  ({size/1e6:.1f} MB text)")
    print(f"loader engines: {available_engines()}")

    # open_graph is cheap: it sniffs format + codec, nothing more.
    # (try it from a shell: PYTHONPATH=src python -m repro.core.source FILE)
    src = open_graph(path, num_vertices=v)
    print(f"opened {src!r}")
    print(f"  info: {src.info().to_dict()}")

    t0 = time.perf_counter()
    el = src.edgelist()                      # host parse (numpy engine)
    t_el = time.perf_counter() - t0
    print(f"edgelist(): {int(el.num_edges):,} edges in "
          f"{t_el*1e3:.0f} ms ({int(el.num_edges)/t_el/1e6:.2f} M edges/s)")

    t0 = time.perf_counter()
    csr = src.csr()                          # fused streaming device build
    t_c = time.perf_counter() - t0
    assert int(csr.offsets[-1]) == e
    print(f"csr() end-to-end (streaming device engine): {t_c*1e3:.0f} ms; "
          f"offsets[-1]={int(csr.offsets[-1]):,}")
    assert src.csr() is src.csr()            # products are memoized

    deg = csr.degrees()
    print(f"degree stats: max={int(deg.max())}, mean={float(deg.mean()):.1f} "
          f"(power law => staged build wins, per the paper)")

    # write once, load many: snapshot the parsed edgelist + prebuilt CSR,
    # then reload with zero parsing and zero building (pure mmap)
    gvel = os.path.join(tmp, "web.gvel")
    snap_src = src.save(gvel)                # returns a handle on the output
    print(f"saved {snap_src!r}")
    t0 = time.perf_counter()
    csr3 = open_graph(gvel).csr()
    t_s = time.perf_counter() - t0
    assert int(csr3.offsets[-1]) == e
    print(f"csr() from .gvel snapshot (embedded CSR, no parse/build): "
          f"{t_s*1e3:.1f} ms ({t_c/max(t_s, 1e-9):.0f}x vs streaming parse)")

    # compressed snapshot: .csr() lazily decodes ONLY the CSR sections
    zgvel = os.path.join(tmp, "web.z.gvel")
    src.save(zgvel, compress="zlib")
    zsrc = open_graph(zgvel)
    print(f"compressed snapshot: {zsrc.info().size_bytes/1e6:.2f} MB "
          f"(codec={zsrc.info().codec})")
    t0 = time.perf_counter()
    csr4 = zsrc.csr()                        # edgelist frames never decoded
    t_z = time.perf_counter() - t0
    assert int(csr4.offsets[-1]) == e
    print(f"csr() from compressed snapshot (lazy, CSR sections only): "
          f"{t_z*1e3:.1f} ms")


if __name__ == "__main__":
    main()
