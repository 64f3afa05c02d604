#!/usr/bin/env python3
"""Upper readings that the limits of ``compare.py`` are set from: the
control of a cell's configuration at the cell's own size.

    python3 bench/readings.py --workload graph500-s20.text \
        --seeds 21,22,23 --out readings.json

For each seed, the control (``compare.control``: the reference put in
the program's place with one guarantee of the configuration broken)
against the reference.  The lower readings are the ``checks`` of the
cell's own runs (``bench/run.py``).  Not part of a benchmark run.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import compare, spec  # noqa: E402
from bench.gen import graphs  # noqa: E402


def control_readings(name: str, seed: int, overrides=None) -> dict:
    cell = spec.load_cell(name, overrides)
    g = graphs.make(cell.config, seed)
    ctl = compare.control(cell.config["control"], g)
    return compare.readings_of(ctl, compare.reference(g), cell.weighted)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = {}
    for s in (int(x) for x in args.seeds.split(",") if x):
        out[s] = control_readings(args.workload, s)
        print(args.workload, "control seed", s, out[s], flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps({"workload": args.workload,
                                          "control": out}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
