"""The exchange+build program's share of its bytes roofline, in percent,
per chip.

With d chips, e = E/d edges and r = ceil(V/d) rows a chip, the bytes it
cannot do without, at 4 bytes an element: each edge's source and target
read from the parse's buffers (2e), written into the send buffers (2e),
received and read by the build (2e), its target written once (e); and
the r + 1 local offsets written once.  Divided by one chip's device time
in the program (the average over the chips).  Unweighted, as the cell
is."""
from bench.metrics import roofline
from bench.metrics.exchange_device_share import PATTERNS


def read(ctx):
    d = ctx.trace.devices
    e, r = ctx.num_edges / d, -(-ctx.num_vertices // d)
    needed = 4 * (2 * e + 2 * e + 2 * e + e) + 4 * (r + 1)
    return roofline(needed, ctx.trace.module_ns(PATTERNS), ctx)
