"""Graph500 spec Kronecker generator: a copy of the program's
``core/generate.rmat_edges`` (N = 2^SCALE, M = edge_factor * N,
A/B/C = 0.57/0.19/0.19, vertex ids permuted from the seed), kept here so
that a change to the program cannot move the benchmark's inputs.
Unweighted, as for Graph500's BFS."""
import numpy as np

from bench.gen.graphs import Graph, rng_for


def kronecker(scale: int, edge_factor: int, *, a: float, b: float,
              c: float, seed: int):
    """``(src, dst, num_vertices)``: int64 ids in ``[0, 2^scale)``.

    Draws the same numbers in the same order as ``rmat_edges`` and makes
    the same comparisons, so it returns the same graph for a seed; only
    the bit arithmetic runs in place, on uint32."""
    rng = rng_for(seed)
    v = 1 << scale
    e = v * edge_factor
    if scale > 31:
        raise ValueError("scale must be at most 31 for uint32 ids")
    src = np.zeros(e, np.uint32)
    dst = np.zeros(e, np.uint32)
    ab, abc = a + b, a + b + c
    t_src = c / (c + (1 - abc)) if (c + (1 - abc)) else 0.5
    t_no_src = a / ab
    r = np.empty(e)
    bit_val = np.empty(e, np.uint32)
    for bit in range(scale):
        rng.random(out=r)
        src_bit = r > ab
        rng.random(out=r)
        hi, lo = r > t_src, r > t_no_src
        dst_bit = lo ^ (src_bit & (hi ^ lo))     # r > (t_src if src_bit else t_no_src)
        for acc, b_ in ((src, src_bit), (dst, dst_bit)):
            np.multiply(b_, np.uint32(1 << bit), out=bit_val, dtype=np.uint32)
            np.bitwise_or(acc, bit_val, out=acc)
    perm = rng.permutation(v)               # de-correlate vertex ids
    return perm[src].astype(np.int64), perm[dst].astype(np.int64), v


def edges(config: dict, seed: int) -> Graph:
    src, dst, v = kronecker(config["scale"], config["edge_factor"],
                            a=config["a"], b=config["b"], c=config["c"],
                            seed=seed)
    return Graph(src, dst, None, v)
