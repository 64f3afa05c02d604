"""Per-layer metric readers, one file each (``bench/metrics/<name>.py``),
found by the metric's name in ``BENCHMARK.json``.

A reader has one function,
``read(ctx: Context) -> float | None``.  It returns ``None`` when the
trace holds nothing it reads (its programs renamed or gone), and the
harness then leaves the metric out of the result's line; it never
stands a 0 in for a missing reading.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from ..profile_reader import Trace


@dataclasses.dataclass(frozen=True)
class Context:
    trace: Trace
    num_vertices: int
    num_edges: int
    weighted: bool
    input_bytes: int              # bytes of the file one load reads
    peaks: Dict[str, Any]         # the chip's row of bench/peaks.json


def share(part_ns: Optional[float], ctx: Context) -> Optional[float]:
    """``part_ns`` as a percentage of the traced window."""
    if part_ns is None or ctx.trace.window_ns <= 0:
        return None
    return 100.0 * part_ns / ctx.trace.window_ns


def roofline(bytes_needed: float, device_ns: Optional[float],
             ctx: Context) -> Optional[float]:
    """Least time the chip's HBM bandwidth allows for ``bytes_needed``,
    as a percentage of the measured device time (bytes-bound)."""
    if not device_ns or "hbm_bytes_per_s" not in ctx.peaks:
        return None
    least_s = bytes_needed / float(ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (device_ns / 1e9)
