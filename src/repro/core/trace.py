"""Named spans on the profiler's clock for the load path.

A span is a ``jax.profiler.TraceAnnotation``: it records only while a
profiler session runs (``jax.profiler.trace`` / ``start_trace``) and
costs well under a microsecond otherwise.  Spans land in the same
``.xplane.pb`` as the device's ``XLA Ops``, on the same clock, so a
stretch in which the device idled can be put down to the span the host
was in.  Names start with ``load.``; docs/performance.md lists them.
"""
from __future__ import annotations

import jax


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """Context manager marking the enclosed host work as ``name``.

    ``stats`` (whole numbers or strings) ride on the span as counters:
    ``jax.profiler.ProfileData`` gives them back as the event's
    ``stats``."""
    return jax.profiler.TraceAnnotation(name, **stats)
