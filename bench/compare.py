"""How ``correct`` is decided: every CSR the window's loads returned,
against the plain reference built from the generator's arrays (never
through a parser).

Each number compared has its limit in the configuration file
(``limits``); ``PERF.md`` gives the readings each limit was set from.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from .gen.reference import RefCSR, csr_np


def reference(graph, *, kind="stable") -> RefCSR:
    """The reference CSR of the generated graph, each weight the float32
    of the whole number the text states.  ``kind`` is the sort's kind:
    only the control passes another."""
    w = None if graph.weights is None else graph.weights.astype(np.float32)
    return csr_np(graph.src, graph.dst, w, graph.num_vertices, kind=kind)


def _mismatch(a, b) -> int:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return max(a.size, b.size, 1)
    return int(np.count_nonzero(a != b))


def _ordered_bits(x: np.ndarray) -> np.ndarray:
    """float32 -> int64 whose differences count ulps across zero too."""
    i = np.asarray(x, np.float32).view(np.int32).astype(np.int64)
    return np.where(i < 0, -(i & 0x7FFFFFFF), i)


def _max_ulp(a, b) -> int:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return 2**31
    if a.size == 0:
        return 0
    if np.isnan(a).any():
        return 2**31
    return int(np.abs(_ordered_bits(a) - _ordered_bits(b)).max())


def readings_of(csr, ref: RefCSR, weighted: bool) -> Dict[str, int]:
    """The numbers compared for one load's CSR."""
    out = {
        "offsets_mismatch": _mismatch(csr.offsets, ref.offsets)
        + (0 if int(csr.num_vertices) == ref.num_vertices else 1),
        "targets_mismatch": _mismatch(csr.targets, ref.targets),
    }
    if weighted:
        out["weights_max_ulp"] = (2**31 if csr.weights is None
                                  else _max_ulp(csr.weights, ref.weights))
    return out


def judge(csrs: Sequence, ref: RefCSR, weighted: bool,
          limits: Dict[str, int]) -> tuple[Dict[str, Dict[str, int]], int]:
    """``(checks, failed)``: each number over all loads (counts summed,
    widest gaps maxed) beside its limit, and the loads that broke one."""
    total: Dict[str, int] = {}
    failed = 0
    for csr in csrs:
        r = readings_of(csr, ref, weighted)
        failed += any(v > limits[k] for k, v in r.items())
        for k, v in r.items():
            total[k] = max(total.get(k, 0), v) if k.endswith("_ulp") \
                else total.get(k, 0) + v
    if not csrs:
        failed = 1
    checks = {k: {"value": v, "limit": limits[k]} for k, v in total.items()}
    return checks, failed


# -- the control: the reference in the program's place, one guarantee broken

def control(name: str, graph) -> RefCSR:
    """``unstable_sort``: the reference's sort without its stability, the
    step a build that sorts faster would take.  ``bfloat16_weights``: the
    weights one precision below the float32 the configuration states."""
    if name == "unstable_sort":
        return reference(graph, kind="quicksort")
    if name == "bfloat16_weights":
        import ml_dtypes
        r = reference(graph)
        w = r.weights.astype(ml_dtypes.bfloat16).astype(np.float32)
        return r._replace(weights=w)
    raise ValueError(f"unknown control {name!r}")


def correct(checks: Dict[str, Dict[str, int]], failed: int,
            attempted: int) -> bool:
    return (attempted > 0 and failed == 0
            and all(c["value"] <= c["limit"] for c in checks.values()))


def lines(checks: Dict[str, Dict[str, int]]) -> List[str]:
    return [f"{k} {c['value']} limit {c['limit']}" for k, c in checks.items()]


