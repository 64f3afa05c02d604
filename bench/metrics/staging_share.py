"""Host staging of the text load's batches (``load.stage``: reading the
blocks off the file or the decompressor into a batch buffer, in the
prefetch thread) as a percentage of the traced load."""
from bench.metrics.spans import span_share


def read(ctx):
    return span_share(ctx, "load.stage")
