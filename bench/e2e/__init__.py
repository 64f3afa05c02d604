"""End-to-end metric readers, one file each (``bench/e2e/<name>.py``),
found by the metric's name in ``BENCHMARK.json``.

A reader has one function, ``read(w: Window) -> float | None``, over
what the harness took itself on the host's clock and from the device:
``None`` when the window holds nothing it reads.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Tuple


@dataclasses.dataclass(frozen=True)
class Window:
    setup_s: float                           # process start to ``start``
    start: float                             # ``time.perf_counter()``
    ops: List[Tuple[float, float, int]]      # (start, end, units) each
    devices: List[Any]                       # the cell's JAX devices
