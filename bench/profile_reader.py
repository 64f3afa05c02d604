"""From a ``jax.profiler`` trace to the events the per-layer metrics read.

A :class:`Trace` keeps four things, all in nanoseconds on the profiler's
one clock: the traced window (the harness's ``bench.op`` annotation
around one operation), the benchmark's own host annotations
(``bench.*``, the driver's phases among them), the device's operations
(``XLA Ops`` lines of the ``/device:TPU:<n>`` planes) and the device's
program runs (``XLA Modules`` lines, names without the ``(<hash>)``
suffix).  It is plain data, so a recorded one can be kept as JSON and
the metric readers tested on it.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.op"          # the harness's annotation of the traced operation
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HASH = re.compile(r"\(\d+\)$")

Event = Tuple[int, str, float, float]          # (device, name, start, end)


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    annotations: List[Tuple[str, float, float]]
    ops: List[Event]
    modules: List[Event]
    devices: int

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def _clip(self, events: Sequence[Event]) -> List[Event]:
        lo, hi = self.window
        return [(d, n, max(s, lo), min(e, hi)) for d, n, s, e in events
                if e > lo and s < hi]

    def busy_ns(self) -> float:
        """Union of the operations' intervals in the window, averaged
        over the devices traced."""
        per_dev: Dict[int, List[Tuple[float, float]]] = {}
        for d, _n, s, e in self._clip(self.ops):
            per_dev.setdefault(d, []).append((s, e))
        total = sum(_union_len(iv) for iv in per_dev.values())
        return total / max(self.devices, 1)

    def module_ns(self, patterns: Sequence[str]) -> Optional[float]:
        """Summed device time of the program runs whose name matches one
        of ``patterns`` (averaged over devices); ``None`` when none does."""
        rx = [re.compile(p) for p in patterns]
        hits = [(s, e) for _d, n, s, e in self._clip(self.modules)
                if any(r.search(n) for r in rx)]
        if not hits:
            return None
        return sum(e - s for s, e in hits) / max(self.devices, 1)

    def idle_gaps(self) -> List[Tuple[str, float, float]]:
        """``(annotation, start, end)`` of every stretch of the window in
        which no operation ran on device 0 (the first traced)."""
        first = min((d for d, *_ in self.ops), default=0)
        busy = sorted((s, e) for d, _n, s, e in self._clip(self.ops)
                      if d == first)
        gaps, t = [], self.window[0]
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            gaps.append((t, self.window[1]))
        return [(self.annotation_at((a + b) / 2), a, b) for a, b in gaps]

    def annotation_at(self, t: float) -> str:
        inside = [(e - s, n) for n, s, e in self.annotations if s <= t <= e]
        return min(inside)[1] if inside else "outside"

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The device operations that took most time and the longest idle
        gaps, each in seconds; a gap is named by the benchmark annotation
        it fell in and its start from the window's start."""
        by_op: Dict[str, float] = {}
        mods = sorted(self._clip(self.modules), key=lambda m: m[2])
        for d, n, s, e in self._clip(self.ops):
            key = f"{_module_at(mods, d, s)}:{short_op(n)}"
            by_op[key] = by_op.get(key, 0.0) + (e - s)
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[1] - g[2])[:top]
        w0 = self.window[0]
        return {
            "device_ops": [[k, v / 1e9 / max(self.devices, 1)] for k, v in ops],
            "idle_gaps": [[f"{n}+{(a - w0) / 1e9:.3f}s", (b - a) / 1e9]
                          for n, a, b in gaps],
        }

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        """A trace kept as ``json.dumps(dataclasses.asdict(trace))``."""
        d = json.loads(text)
        return cls(tuple(d["window"]),
                   [tuple(a) for a in d["annotations"]],
                   [tuple(o) for o in d["ops"]],
                   [tuple(m) for m in d["modules"]], d["devices"])


def short_op(name: str) -> str:
    """``%fusion.38 = s32[...] fusion(...)`` -> ``fusion.38``."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def _module_at(mods: List[Event], dev: int, t: float) -> str:
    for d, n, s, e in mods:
        if d == dev and s <= t <= e:
            return n
    return "-"


def _union_len(iv: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(iv):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def read_trace(log_dir: str) -> Trace:
    """The :class:`Trace` of the newest ``.xplane.pb`` under ``log_dir``."""
    import jax

    files = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    annotations: List[Tuple[str, float, float]] = []
    ops: List[Event] = []
    modules: List[Event] = []
    devices = set()
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if m:
                    dev = int(m.group(1))
                    if line.name == "XLA Ops":
                        ops.append((dev, ev.name, s, e))
                        devices.add(dev)
                    elif line.name == "XLA Modules":
                        modules.append((dev, _HASH.sub("", ev.name), s, e))
                elif ev.name.startswith("bench."):
                    annotations.append((ev.name, s, e))
    windows = [(s, e) for n, s, e in annotations if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"trace under {log_dir} has {len(windows)} "
                         f"{WINDOW} annotations, not one")
    if not ops:
        raise ValueError(f"trace under {log_dir} has no device operations")
    return Trace(windows[0], sorted(annotations, key=lambda a: a[1]),
                 ops, modules, max(len(devices), 1))
