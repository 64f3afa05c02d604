"""Loads back to back, each through a fresh handle:
``open_graph(path, **kw)``, ``.csr()``, ``jax.block_until_ready``.
The file format is the traffic's ``input`` (``bench/inputs/``); every
CSR a load returned is compared with the plain reference after the
window (``bench/compare.py``)."""
import os
import shutil

from bench import compare
from bench.spec import plugin


class LoadCSR:
    def __init__(self, cell, graph, workdir):
        self.cell, self.graph = cell, graph
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        self.files = plugin("inputs", cell.traffic["input"]).write(
            graph, cell.config, cell.traffic, workdir)
        self.units = graph.num_edges
        self.input_bytes = self.files.input_bytes

    def op(self, k, mark):
        import jax

        from repro.core import open_graph

        with mark("bench.open"):
            g = open_graph(self.files.path(k), **self.files.open_kwargs)
        with mark("bench.csr"):
            csr = g.csr()
        with mark("bench.ready"):
            jax.block_until_ready((csr.offsets, csr.targets, csr.weights))
        return csr

    def check(self, results):
        ref = compare.reference(self.graph)
        return compare.judge(results, ref, self.cell.weighted,
                             self.cell.config["limits"])


def prepare(cell, graph, workdir):
    return LoadCSR(cell, graph, workdir)
