"""Device time of the fused parse program (``core/parse.py``,
``_parse_accumulate_impl``) as a percentage of the traced load."""
from bench.metrics import share

PATTERNS = (r"^jit__parse_accumulate_impl$",)


def read(ctx):
    return share(ctx.trace.module_ns(PATTERNS), ctx)
