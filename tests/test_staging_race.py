"""Staged host bytes are never written again once handed to the device.

The streaming loader stages batch i+1 while the device parses batch i.
A host-to-device transfer may read its host bytes after ``put`` returns
(and on the CPU backend a contiguous array can be aliased, not copied),
so a staging buffer that is reused for a later batch can change the
bytes of a batch still in flight: the load then returns a wrong CSR now
and then.  Every batch is therefore staged into a buffer of its own.

Two checks: a deterministic one that no two batches of one load share
host memory and that every batch's bytes are intact after the load; and
a witness that loads the same text many times with one-block batches
(whose staged views are contiguous) and compares every CSR with
``csr_np``.
"""
import functools

import numpy as np
import pytest

from repro.core import loader, open_graph
from repro.core.blocks import MemoryBlockSource, plan_blocks, stage_blocks
from repro.core.build import csr_np
from repro.core.generate import write_edgelist

WITNESS_LOADS = 30


class RecordingSource(MemoryBlockSource):
    """A block source that keeps every batch it staged, with its ids."""

    def __init__(self, data):
        super().__init__(data)
        self.staged = []

    def stage(self, plan, block_ids, check_lines=False):
        out = super().stage(plan, block_ids, check_lines)
        self.staged.append((np.asarray(block_ids).copy(), out))
        return out


@pytest.mark.parametrize("prefetch", [True, False])
def test_batches_never_share_host_buffer(prefetch):
    rng = np.random.default_rng(5)
    src = rng.integers(1, 500, 3000)
    dst = rng.integers(1, 500, 3000)
    text = "".join(f"{s} {d}\n" for s, d in zip(src, dst)).encode()
    data = np.frombuffer(text, np.uint8)
    plan = plan_blocks(len(data), beta=1024, overlap=64)
    source = RecordingSource(data)
    cap = plan.num_blocks * plan.edge_cap
    acc_src, acc_dst, _w, total = loader._parse_span(
        source, plan, 0, plan.num_blocks, weighted=False, base=1,
        batch_blocks=2, parse="xla", cap=cap, prefetch=prefetch)
    batches = source.staged
    assert len(batches) == -(-plan.num_blocks // 2) >= 4
    for i, (_ids, a) in enumerate(batches):
        for _jds, b in batches[i + 1:]:
            assert not np.may_share_memory(a, b)
    # after the whole load, each batch still holds the bytes it staged
    for ids, view in batches:
        assert np.array_equal(view, stage_blocks(data, plan, ids))
    n = int(total)
    assert n == len(src)
    assert np.array_equal(np.asarray(acc_src[:n]), src - 1)
    assert np.array_equal(np.asarray(acc_dst[:n]), dst - 1)


@pytest.fixture(scope="module")
def witness_graph(tmp_path_factory):
    """2^18 uniform random edges over 2^14 vertices, as 1-based text."""
    rng = np.random.default_rng(1234)
    v, e = 1 << 14, 1 << 18
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    path = str(tmp_path_factory.mktemp("witness") / "g.el")
    write_edgelist(path, src, dst, base=1)
    return path, v, csr_np(src, dst, None, v)


@pytest.mark.parametrize("prefetch", [True, False])
@pytest.mark.parametrize("batch_blocks", [1, 8])
def test_repeated_loads_equal_csr_np(witness_graph, monkeypatch,
                                     batch_blocks, prefetch):
    path, v, ref = witness_graph
    if not prefetch:
        monkeypatch.setattr(loader, "_parse_span", functools.partial(
            loader._parse_span, prefetch=False))
    wrong = []
    for k in range(WITNESS_LOADS):
        csr = open_graph(path, num_vertices=v, beta=1 << 14,
                         batch_blocks=batch_blocks).csr()
        if not (np.array_equal(csr.offsets, ref.offsets)
                and np.array_equal(csr.targets, ref.targets)):
            wrong.append(k)
    assert wrong == [], f"{len(wrong)} of {WITNESS_LOADS} loads wrong"
