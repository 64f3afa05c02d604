"""Setting up the text load's packed edge accumulators
(``load.accumulators``: filled on the host, then put on the device) as a
percentage of the traced load."""
from bench.metrics.spans import span_share


def read(ctx):
    return span_share(ctx, "load.accumulators")
