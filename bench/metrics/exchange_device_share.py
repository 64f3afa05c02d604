"""Device time of the mesh's exchange+build program
(``core/distributed.py``: bucketing by owner, the ``all_to_all`` and
each chip's local CSR build, module ``jit_exchange_build``), averaged
over the chips, as a percentage of the traced load."""
from bench.metrics import share

PATTERNS = (r"^jit_exchange_build$",)


def read(ctx):
    return share(ctx.trace.module_ns(PATTERNS), ctx)
