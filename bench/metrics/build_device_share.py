"""Device time of the CSR build programs (``core/build.py``:
``csr_staged``, ``csr_binned``, ``csr_global``) as a percentage of the
traced load."""
from bench.metrics import share

PATTERNS = (r"^jit_csr_(staged|binned|global)$",)


def read(ctx):
    return share(ctx.trace.module_ns(PATTERNS), ctx)
