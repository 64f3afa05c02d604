"""A road network in the shape of the 9th DIMACS Challenge's
``USA-road-d`` graphs, its topology stood in for by a 2D grid.

Each road segment (a grid edge, right or down from a cell) is two arcs,
``u v d`` then ``v u d``, on consecutive lines with the same integer
distance ``d``, as in the challenge's ``.gr`` files.  The segments'
order, the vertex ids (a permutation of the grid's) and the distances
(uniform integers in ``[weight_min, weight_max]``) are drawn from the
seed, in that order.
"""
import numpy as np

from bench.gen.graphs import Graph, rng_for


def edges(config: dict, seed: int) -> Graph:
    side = config["side"]
    v = side * side
    idx = np.arange(v).reshape(side, side)
    a = np.concatenate([idx[:, :-1].ravel(), idx[:-1, :].ravel()])
    b = np.concatenate([idx[:, 1:].ravel(), idx[1:, :].ravel()])
    rng = rng_for(seed)
    order = rng.permutation(a.shape[0])
    perm = rng.permutation(v)
    d = rng.integers(config["weight_min"], config["weight_max"] + 1,
                     size=a.shape[0])
    u, x = perm[a[order]], perm[b[order]]
    return Graph(np.stack([u, x], 1).ravel(), np.stack([x, u], 1).ravel(),
                 np.repeat(d, 2), v)
