"""The CSR build programs' share of their bytes roofline, in percent.

Bytes a build cannot do without, at 4 bytes an element: each edge's
source, target and (weighted) weight read once, its target and weight
written once, and the V + 1 offsets written once."""
from bench.metrics import roofline

PATTERNS = (r"^jit_csr_(staged|binned|global)$",)


def read(ctx):
    per_edge = 3 if ctx.weighted else 2
    needed = 4 * (ctx.num_edges * per_edge + ctx.num_edges * (per_edge - 1)
                  + ctx.num_vertices + 1)
    return roofline(needed, ctx.trace.module_ns(PATTERNS), ctx)
