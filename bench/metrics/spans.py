"""The program's own spans (``load.*``, ``repro.core.trace``) for the
readers that need them.

:class:`bench.profile_reader.Trace` keeps only the harness's ``bench.*``
host events, so these readers find the spans themselves: in the
``.xplane.pb`` that ``bench/run.py --trace 1`` writes under
``bench/.data/<cell>/trace`` and keeps while its readers run, the one
whose ``bench.op`` window is the trace's.  A :class:`SpanTrace` carries
its spans along instead (a recorded trace kept as JSON, a synthetic one
in a test).  A trace with no spans, such as one of a program that has
none, gives every reader ``None``.

Shares are unions, so nested or overlapping spans of one name (one per
thread) count once."""
from __future__ import annotations

import dataclasses
import glob
import json
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench.metrics import share
from bench.profile_reader import _DEVICE_PLANE, WINDOW, Trace, _union_len

PREFIX = "load."
LOGS = Path(__file__).resolve().parents[2] / "bench" / ".data"

Span = Tuple[str, float, float]                 # (name, start, end)

_found: Dict[Tuple[float, float], List[Span]] = {}


@dataclasses.dataclass
class SpanTrace(Trace):
    """A :class:`Trace` with the program's spans, in nanoseconds on the
    same clock."""
    spans: List[Span] = dataclasses.field(default_factory=list)

    @classmethod
    def from_json(cls, text: str) -> "SpanTrace":
        """A trace kept as ``json.dumps(dataclasses.asdict(trace))``; a
        kept :class:`Trace` reads with no spans."""
        t = super().from_json(text)
        t.spans = [tuple(p) for p in json.loads(text).get("spans", [])]
        return t


def find_spans(window: Tuple[float, float],
               root: Optional[Path] = None) -> List[Span]:
    """The ``load.*`` host events of the ``.xplane.pb`` under
    ``root/*/trace`` (``root`` defaults to :data:`LOGS`) whose
    ``bench.op`` window is ``window``, sorted by start; empty where there
    is none."""
    import jax

    root = LOGS if root is None else root
    for f in sorted(glob.glob(f"{root}/*/trace/**/*.xplane.pb",
                              recursive=True)):
        data = jax.profiler.ProfileData.from_file(f)
        here, out = None, []
        for plane in data.planes:
            if _DEVICE_PLANE.match(plane.name):
                continue
            for line in plane.lines:
                for ev in line.events:
                    s = float(ev.start_ns)
                    e = s + float(ev.duration_ns)
                    if ev.name == WINDOW:
                        here = (s, e)
                    elif ev.name.startswith(PREFIX):
                        out.append((ev.name, s, e))
        if here == tuple(window):
            return sorted(out, key=lambda p: p[1])
    return []


def spans(trace: Trace) -> List[Span]:
    """The trace's program spans: its own where it is a
    :class:`SpanTrace`, else those :func:`find_spans` finds (once a
    window)."""
    if isinstance(trace, SpanTrace):
        return trace.spans
    key = tuple(trace.window)
    if key not in _found:
        _found[key] = find_spans(key)
    return _found[key]


def span_share(ctx, name: str) -> Optional[float]:
    """The union of the ``name`` spans in the window as a percentage of
    it; ``None`` where the window holds none."""
    lo, hi = ctx.trace.window
    iv = [(max(s, lo), min(e, hi)) for n, s, e in spans(ctx.trace)
          if n == name and e > lo and s < hi]
    return share(_union_len(iv), ctx) if iv else None


def idle_by_span(trace: Trace) -> Dict[Optional[str], float]:
    """Device 0's idle time in the window (the gaps of
    :meth:`Trace.idle_gaps`), in nanoseconds, under each innermost
    program span (the shortest that covers it, whatever its thread), and
    under none (key ``None``)."""
    sp = spans(trace)
    out: Dict[Optional[str], float] = {}
    for _a, lo, hi in trace.idle_gaps():
        cuts = sorted({lo, hi, *(t for _n, s, e in sp
                                 for t in (s, e) if lo < t < hi)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inside = [(e - s, n) for n, s, e in sp if s <= mid <= e]
            key = min(inside)[1] if inside else None
            out[key] = out.get(key, 0.0) + (b - a)
    return out
