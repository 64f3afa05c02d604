"""Device time of the ``all-to-all`` operations (the mesh exchange's
collective, instructions ``all_to_all.<n>`` in XLA's names), the union
of their intervals on each chip averaged over the chips, as a
percentage of the traced load: the collective's exposed time."""
import re

from bench.metrics import share
from bench.profile_reader import _union_len, short_op

OP = re.compile(r"^all[-_]to[-_]all")


def read(ctx):
    t = ctx.trace
    per_dev = {}
    for d, n, s, e in t._clip(t.ops):
        if OP.search(short_op(n)):
            per_dev.setdefault(d, []).append((s, e))
    if not per_dev:
        return None
    return share(sum(_union_len(iv) for iv in per_dev.values())
                 / max(t.devices, 1), ctx)
