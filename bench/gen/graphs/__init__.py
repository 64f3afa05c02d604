"""Graph families of the benchmark, seeded and vectorised, one file
each (``bench/gen/graphs/<generator>.py``), found by the configuration's
``generator``.

A family module has one function, ``edges(config, seed)``, which
returns :class:`Graph` arrays drawn from :func:`rng_for` ``(seed)``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from ...spec import plugin


class Graph(NamedTuple):
    src: np.ndarray                  # (E,) int64, 0-based, in line order
    dst: np.ndarray
    weights: Optional[np.ndarray]    # (E,) int64 as the text states them
    num_vertices: int

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])


def rng_for(seed: int) -> np.random.Generator:
    """The one generator a run draws from; any whole number is a seed."""
    return np.random.default_rng(abs(int(seed)))


def make(config: dict, seed: int) -> Graph:
    """The graph of ``config`` for ``seed``, checked against the sizes
    the configuration states."""
    g = plugin("gen/graphs", config["generator"]).edges(config, seed)
    if g.num_vertices != config["num_vertices"] \
            or g.num_edges != config["num_edges"]:
        raise ValueError(f"{config['name']}: generator gave V={g.num_vertices} "
                         f"E={g.num_edges}, the configuration states "
                         f"V={config['num_vertices']} E={config['num_edges']}")
    if (g.weights is not None) != bool(config["weighted"]):
        raise ValueError(f"{config['name']}: weights do not match 'weighted'")
    return g
