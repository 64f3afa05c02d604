"""The host side of the host-to-device transfers (``load.put``: each
staged batch of a text load, each edge section of a ``.gvel`` load) as a
percentage of the traced load."""
from bench.metrics.spans import span_share


def read(ctx):
    return span_share(ctx, "load.put")
