"""The share of the exchange's receive slots that hold an edge, in
percent: ``edges / (shards^2 * send_cap)`` from the counters of the
``load.exchange`` span (``core/distributed.py``).  The rest is the
padding of ``send_cap``'s rounding, which each chip's build sorts."""
import glob
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench.metrics.spans import LOGS
from bench.profile_reader import _DEVICE_PLANE, WINDOW

SPAN = "load.exchange"
KEYS = ("shards", "send_cap", "edges")


def find_stats(window: Tuple[float, float], name: str,
               root: Optional[Path] = None) -> List[Dict[str, float]]:
    """The counters of each ``name`` host event in the ``.xplane.pb``
    under ``root/*/trace`` (``root`` defaults to the harness's data
    directory) whose ``bench.op`` window is ``window``."""
    import jax

    root = LOGS if root is None else root
    for f in sorted(glob.glob(f"{root}/*/trace/**/*.xplane.pb",
                              recursive=True)):
        here, out = None, []
        for plane in jax.profiler.ProfileData.from_file(f).planes:
            if _DEVICE_PLANE.match(plane.name):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        s = float(ev.start_ns)
                        here = (s, s + float(ev.duration_ns))
                    elif ev.name == name:
                        out.append(dict(ev.stats))
        if here == tuple(window):
            return out
    return []


def read(ctx):
    for st in find_stats(ctx.trace.window, SPAN):
        if all(k in st for k in KEYS):
            slots = st["shards"] ** 2 * st["send_cap"]
            return 100.0 * st["edges"] / slots if slots else None
    return None
