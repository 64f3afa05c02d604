"""The fused parse program's share of its bytes roofline, in percent.

Bytes the parse cannot do without: the file read once, and 4 bytes of
each edge's source, destination and (weighted) weight written once.  The
count is the same whatever implements the parse."""
from bench.metrics import roofline

PATTERNS = (r"^jit__parse_accumulate_impl$",)


def read(ctx):
    written = 4 * ctx.num_edges * (3 if ctx.weighted else 2)
    return roofline(ctx.input_bytes + written,
                    ctx.trace.module_ns(PATTERNS), ctx)
