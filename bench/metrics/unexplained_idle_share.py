"""Device 0's idle time that falls under none of the program's spans
(``load.*``), as a percentage of the traced load: the part of the gaps
``device_idle_share`` counts that no span explains."""
from bench.metrics import share
from bench.metrics.spans import idle_by_span, spans


def read(ctx):
    if not spans(ctx.trace) or not ctx.trace.ops:
        return None
    return share(idle_by_span(ctx.trace).get(None, 0.0), ctx)
