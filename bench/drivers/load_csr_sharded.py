"""Loads back to back into a CSR sharded by vertex range over a 1-D
mesh of the cell's chips, each through a fresh handle:
``open_graph(path, **kw).csr_sharded(mesh)``, ``jax.block_until_ready``,
then each chip's offsets and the valid prefix of its targets copied to
the host, so that no load's device buffers outlive it.  After the
window the global CSR is assembled from those copies, by the layout
docs/distributed.md sets out, and compared with the plain reference
(``bench/compare.py``)."""
import os
import shutil
from typing import List, NamedTuple

import numpy as np

from bench import compare
from bench.gen.reference import RefCSR
from bench.spec import plugin


class HostShards(NamedTuple):
    """One load's result on the host, in shard order (unweighted, as the
    cell is; a weighted cell's check would find no weights and fail)."""
    offsets: List[np.ndarray]            # shard k's local offsets
    targets: List[np.ndarray]            # its targets, valid prefix only
    num_vertices: int


def _in_shard_order(arr):
    """The per-device pieces of an array sharded on its first axis."""
    shards = sorted(arr.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    return [s.data for s in shards]


def to_host(csr) -> HostShards:
    """Shard k's offsets hold ``rows + 1`` entries from 0, the last its
    edge count n; its targets are read up to n."""
    offsets = [np.asarray(o)[0] for o in _in_shard_order(csr.offsets)]
    valid = [t[0, :int(o[-1])]
             for t, o in zip(_in_shard_order(csr.targets), offsets)]
    for t in valid:
        t.copy_to_host_async()
    return HostShards(offsets, [np.asarray(t) for t in valid],
                      int(csr.num_vertices))


def assemble(shards: HostShards) -> RefCSR:
    """The global CSR of one load.  With d shards and V vertices, shard
    k owns rows ``[k*rows, (k+1)*rows)`` of ``rows = ceil(V/d)`` (the
    last shard's rows past V hold no edge); its local offsets start at
    0, so global offsets add the edges of the shards before it, and the
    targets are the shards' targets end to end."""
    v, d = shards.num_vertices, len(shards.offsets)
    rows = -(-v // d)
    offsets, base = [], 0
    for k, off in enumerate(shards.offsets):
        owned = max(min(rows, v - k * rows), 0)
        offsets.append(off[:owned].astype(np.int64) + base)
        base += int(off[-1])
    offsets.append(np.array([base], np.int64))
    return RefCSR(np.concatenate(offsets), np.concatenate(shards.targets),
                  None, v)


class LoadCSRSharded:
    def __init__(self, cell, graph, workdir):
        import jax

        width = int(cell.traffic["mesh_width"])
        if width != cell.chips:
            raise ValueError(f"{cell.name}: a mesh of {width} over "
                             f"{cell.chips} chip(s)")
        self.axis = cell.traffic["mesh_axis"]
        self.mesh = jax.sharding.Mesh(np.array(jax.devices()[:width]),
                                      (self.axis,))
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
            csr = g.csr_sharded(self.mesh, axis=self.axis)
        with mark("bench.ready"):
            jax.block_until_ready((csr.offsets, csr.targets))
        with mark("bench.copy"):
            return to_host(csr)

    def check(self, results):
        ref = compare.reference(self.graph)
        return compare.judge([assemble(r) for r in results], ref,
                             self.cell.weighted, self.cell.config["limits"])


def prepare(cell, graph, workdir):
    return LoadCSRSharded(cell, graph, workdir)
