"""An edgelist-only ``.gvel`` snapshot, written straight from the
generator's arrays, and hard links to it: consecutive loads never share
a path, since the program's snapshot engine memoises the one file it
opened last."""
import os

import numpy as np

from bench.gen import writers
from bench.inputs import Files

LINKS = 8


def write(graph, config, traffic, workdir) -> Files:
    first = os.path.join(workdir, "graph0.gvel")
    w = None if graph.weights is None else graph.weights.astype(np.float32)
    size = writers.write_gvel(first, graph.src, graph.dst,
                              graph.num_vertices, w)
    paths = [first]
    for k in range(1, LINKS):
        paths.append(os.path.join(workdir, f"graph{k}.gvel"))
        os.link(first, paths[-1])
    return Files(paths, size, {"weighted": True} if w is not None else {})
