"""The plain reference CSR: a copy of the program's ``build.csr_np``.

A stable argsort by source, so the targets of a row keep the order of
the input's lines.  Kept here so that a change to ``core/build.py``
cannot move the yardstick.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np


class RefCSR(NamedTuple):
    offsets: np.ndarray          # (V + 1,) int64
    targets: np.ndarray          # (E,) int32
    weights: Optional[np.ndarray]
    num_vertices: int


def csr_np(src: np.ndarray, dst: np.ndarray, weights: Optional[np.ndarray],
           num_vertices: int, *, kind: str = "stable") -> RefCSR:
    """Host reference CSR.  ``kind`` is numpy's sort kind; only the
    control passes another than ``stable``."""
    m = src >= 0
    src, dst = src[m], dst[m]
    weights = weights[m] if weights is not None else None
    order = np.argsort(src, kind=kind)
    deg = np.bincount(src, minlength=num_vertices)
    offsets = np.zeros(num_vertices + 1, np.int64)
    np.cumsum(deg, out=offsets[1:])
    return RefCSR(offsets, dst[order].astype(np.int32),
                  None if weights is None else weights[order],
                  num_vertices)
