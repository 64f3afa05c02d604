"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  The roofline analysis
(benchmarks/roofline.py) is separate because it consumes dry-run
artifacts rather than wall-clock timings.

  PYTHONPATH=src python -m benchmarks.run [fig1 fig2 ...]
"""
from __future__ import annotations

import sys

from . import (e2e_load_csr, fig1_parse_approaches, fig2_block_size,
               fig3_strategies, fig4_partitions, fig5_csr_frameworks,
               fig7_edgelist, fig8_breakdown, fig9_scaling)

SUITES = {
    "fig1": fig1_parse_approaches.run,
    "fig2": fig2_block_size.run,
    "fig3": fig3_strategies.run,
    "fig4": fig4_partitions.run,
    "fig5": fig5_csr_frameworks.run,
    "fig7": fig7_edgelist.run,
    "fig8": fig8_breakdown.run,
    "fig9": fig9_scaling.run,
    "e2e": e2e_load_csr.run,
}


def main() -> None:
    from repro.core import env
    env.use_compilation_cache()
    want = sys.argv[1:] or list(SUITES)
    print("name,us_per_call,derived")
    for name in want:
        SUITES[name]()


if __name__ == "__main__":
    main()
