"""Device idle time under the mesh load's host syncs (``load.shard_join``:
the main thread waiting for each shard's parse and edge count;
``load.bucket_histogram``; ``load.assemble``), on each chip, averaged
over the chips, as a percentage of the traced load.  A chip whose shard
finished first idles here while the slowest shard parses."""
from bench.metrics import share
from bench.metrics.spans import spans
from bench.profile_reader import _union_len

SYNCS = ("load.shard_join", "load.bucket_histogram", "load.assemble")


def read(ctx):
    t = ctx.trace
    lo, hi = t.window
    under = [(max(s, lo), min(e, hi)) for n, s, e in spans(t)
             if n in SYNCS and e > lo and s < hi]
    if not under or not t.ops:
        return None
    busy = {}
    for d, _n, s, e in t._clip(t.ops):
        busy.setdefault(d, []).append((s, e))
    # on a chip, the idle time under the spans is what they add to its
    # busy intervals
    idle = sum(_union_len(under + iv) - _union_len(iv)
               for iv in busy.values())
    return share(idle / max(t.devices, 1), ctx)
