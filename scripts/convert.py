#!/usr/bin/env python
"""Convert a text edgelist or MatrixMarket file to a ``.gvel`` snapshot.

GVEL's "write once, load many": pay the text parse once here, then every
load on the output is a zero-parse mmap (and, with the default embedded
CSR, ``open_graph(out).csr()`` skips the build entirely).

  PYTHONPATH=src python scripts/convert.py graph.el graph.gvel
  PYTHONPATH=src python scripts/convert.py --weighted --base 0 g.el g.gvel
  PYTHONPATH=src python scripts/convert.py matrix.mtx matrix.gvel

A thin shell over the :class:`repro.core.source.GraphSource` API:
``open_graph(input, ...).save(output, ...)``.  Formats are sniffed by
magic (MTX banner through gzip/framed compression too); MTX
field/symmetry attributes are honored — the snapshot stores the
resolved graph.  See docs/snapshot-format.md for the container spec and
docs/api.md for the API.  Refuses to overwrite an existing output
unless ``--force`` is given.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Convert a text edgelist / MTX file to a .gvel snapshot")
    ap.add_argument("input", help="text edgelist or MatrixMarket file")
    ap.add_argument("output", help="output .gvel path")
    ap.add_argument("--weighted", action="store_true",
                    help="parse a third weight column (text inputs; MTX "
                    "weighting comes from the banner)")
    ap.add_argument("--symmetric", action="store_true",
                    help="materialize reverse edges (text inputs; MTX "
                    "symmetry comes from the banner)")
    ap.add_argument("--base", type=int, default=1, choices=(0, 1),
                    help="vertex-id base of the text input (default 1)")
    ap.add_argument("--num-vertices", type=int, default=None,
                    help="|V| override for text inputs (default max id + 1, "
                    "which drops isolated trailing vertices); MTX inputs "
                    "take |V| from the size line")
    ap.add_argument("--engine", default="numpy",
                    help="parse engine for the conversion read (default "
                    "numpy; see repro.core.available_engines())")
    ap.add_argument("--no-csr", action="store_true",
                    help="store only the packed edgelist, not a prebuilt CSR")
    ap.add_argument("--compress", default=None, metavar="CODEC[:LEVEL]",
                    help="store sections compressed (.gvel v2): zlib always, "
                    "zstd when the zstandard package is installed; e.g. "
                    "--compress zlib or --compress zstd:9")
    ap.add_argument("--force", action="store_true",
                    help="overwrite an existing output file")
    args = ap.parse_args(argv)

    if os.path.exists(args.output) and not args.force:
        print(f"error: refusing to overwrite existing {args.output} "
              f"(pass --force to replace it)", file=sys.stderr)
        return 2

    from repro.core import open_graph

    try:
        t0 = time.perf_counter()
        # format probe only (validate=False: the real open below, with
        # the engine pinned, does the header validation once)
        src = open_graph(args.input, validate=False)
        if src.format == "mtx":
            ignored = [name for name, off_default in
                       [("--weighted", not args.weighted),
                        ("--symmetric", not args.symmetric),
                        ("--base", args.base == 1),
                        ("--num-vertices", args.num_vertices is None)]
                       if not off_default]
            if ignored:
                print(f"warning: {', '.join(ignored)} ignored for MTX input "
                      f"— field/symmetry/base/|V| come from the MTX header",
                      file=sys.stderr)
            src = open_graph(args.input, engine=args.engine)
        else:
            src = open_graph(args.input, engine=args.engine,
                             weighted=args.weighted,
                             symmetric=args.symmetric, base=args.base,
                             num_vertices=args.num_vertices)
        out = src.save(args.output, compress=args.compress,
                       csr=not args.no_csr)
        # eager re-read of what we just wrote: decompress + CRC-check
        # every section now, not lazily at some consumer's first access
        from repro.core import read_snapshot
        read_snapshot(args.output)
        t_convert = time.perf_counter() - t0
    except (OSError, ValueError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 1

    info = out.info()
    in_sz = os.path.getsize(args.input)
    comp = f" codec={info.codec}" if info.codec else ""
    print(f"{args.input} ({in_sz / 1e6:.2f} MB) -> {args.output} "
          f"({info.size_bytes / 1e6:.2f} MB, "
          f"{info.size_bytes / max(in_sz, 1):.2f}x input)"
          f"{comp} in {t_convert * 1e3:.0f} ms")
    print(f"  |V|={info.num_vertices:,} |E|={info.num_edges:,} "
          f"v{info.version} weighted={info.weighted} "
          f"edgelist={info.has_edgelist} csr={info.has_csr}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
