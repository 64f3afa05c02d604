"""Share of the traced load in which no operation ran on the device:
1 - (union of the ``XLA Ops`` intervals) / (``bench.open`` start to
``bench.ready`` end), in percent."""


def read(ctx):
    if not ctx.trace.ops or ctx.trace.window_ns <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_ns() / ctx.trace.window_ns)
