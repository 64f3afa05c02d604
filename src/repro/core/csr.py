"""End-to-end CSR reading: file -> EdgeList -> CSR (GVEL csr-partition-rho).

``convert_to_csr`` turns an in-memory EdgeList into a CSR with the staged
build (``build.csr_staged``) or, on the host, the numpy oracle;
``read_csr`` is the file -> CSR wrapper over the unified loader.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from . import build
from .types import CSR, EdgeList


def convert_to_csr(el: EdgeList, *, engine: str = "jax") -> CSR:
    """Convert an in-memory EdgeList to CSR.

    engine: 'jax' (the staged device build) | 'numpy' (the host oracle)
    """
    n = int(el.num_edges)
    v = el.num_vertices
    weighted = el.weights is not None
    if engine == "numpy":
        s = np.asarray(el.src[:n])
        d = np.asarray(el.dst[:n])
        w = None if not weighted else np.asarray(el.weights[:n])
        return build.csr_np(s, d, w, v)
    src = jnp.asarray(el.src[:n])
    dst = jnp.asarray(el.dst[:n])
    w = jnp.asarray(el.weights[:n]) if weighted else None
    offsets, targets, ww = build.csr_staged(src, dst, w, v, weighted=weighted)
    return CSR(np.asarray(offsets), np.asarray(targets),
               None if ww is None else np.asarray(ww), v)


def read_csr(
    path: str,
    *,
    weighted: bool = False,
    symmetric: bool = False,
    base: int = 1,
    num_vertices: Optional[int] = None,
    engine: str = "jax",
    **reader_kwargs,
) -> CSR:
    """File -> CSR through the unified loader (back-compat wrapper).

    ``engine="jax"`` maps to the streaming ``device`` engine, whose
    parse -> CSR path is fused on device; see loader.load_csr.  Binary
    ``.gvel`` snapshots are detected by magic in the front door and
    served zero-parse (an embedded CSR skips the build entirely).
    """
    from .loader import load_csr
    return load_csr(path, engine="device" if engine == "jax" else engine,
                    weighted=weighted, symmetric=symmetric, base=base,
                    num_vertices=num_vertices, **reader_kwargs)


def csr_to_dense(csr: CSR) -> np.ndarray:
    """Small-graph debugging helper."""
    v = csr.num_vertices
    out = np.zeros((csr.num_rows, v), np.int64)
    off = np.asarray(csr.offsets)
    tgt = np.asarray(csr.targets)
    for u in range(csr.num_rows):
        for t in tgt[off[u]:off[u + 1]]:
            out[u, t] += 1
    return out
