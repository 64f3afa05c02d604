"""Copying the built CSR back to the host (``load.copy_back``: offsets,
targets and weights) as a percentage of the traced load.  It begins by
waiting for the build, so it holds the build's tail as well."""
from bench.metrics.spans import span_share


def read(ctx):
    return span_share(ctx, "load.copy_back")
