"""Pallas kernels for GVEL's compute hot spots.

Each kernel package ships kernel.py (pl.pallas_call + explicit BlockSpec
VMEM tiling), ops.py (jit'd wrapper with an XLA fallback), and ref.py
(pure-jnp oracle used by the allclose test sweeps).

  parse_edges       text block -> packed edges (GVEL Alg. 1 hot loop;
                    interpret mode only, it does not lower for TPU yet)
  degree_histogram  contention-free degree counting (rho-partition analogue)
  exclusive_scan    degrees -> CSR offsets (Alg. 2 exclusiveScan)
  neighbor_gather   batched CSR row gather (sampler consumer of the CSR)
"""
from .parse_edges import parse_edges, parse_edges_accumulate, parse_edges_ref
from .degree_histogram import degree_histogram, degree_histogram_ref
from .exclusive_scan import csr_offsets, exclusive_scan, exclusive_scan_ref
from .neighbor_gather import neighbor_gather, neighbor_gather_ref

__all__ = [
    "parse_edges", "parse_edges_accumulate", "parse_edges_ref",
    "degree_histogram", "degree_histogram_ref",
    "exclusive_scan", "csr_offsets", "exclusive_scan_ref",
    "neighbor_gather", "neighbor_gather_ref",
]
