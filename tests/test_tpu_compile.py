"""The main loading path, compiled for one chip of a described TPU v5e.

Nothing here runs on a chip.  Each test lowers and compiles a program
for a ``v5e:2x2`` topology that is described, not attached, at the
geometry the loader uses on a Graph500 scale-22 text load: 256 KiB
blocks, 8 blocks a batch, 2^27-slot edge buffers, 2^22 vertices; and
the sharded load's exchange over the topology's four chips.  A
program that the TPU compiler refuses, or that outgrows a v5e's HBM,
fails here at no chip time.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
pytest-xdist worker imports every test file.  Keep all such compiles in
this one file, so that one worker takes them all.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import build, distributed, loader, parse
from repro.kernels.parse_edges.kernel import parse_bytes_kernel

V5E_HBM_BYTES = 16 * 2**30
NB = loader.DEFAULT_BATCH_BLOCKS
BUF_LEN = loader.DEFAULT_BETA + loader.DEFAULT_OVERLAP
CAP = 1 << 27
V = 1 << 22


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")      # else the compiler logs to /tmp
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(mem):
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def test_fused_parse_compiles_for_v5e(one_chip, no_compile_cache):
    """The ``device`` engine's per-batch program, donated accumulators."""
    acc = _spec(one_chip, (CAP,), jnp.int32)
    owned = _spec(one_chip, (NB,), jnp.int32)
    compiled = parse._parse_accumulate_jit(True).lower(
        acc, acc, None, _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (NB, BUF_LEN), jnp.uint8), owned, owned,
        weighted=False, base=1, edge_bound=NB * (BUF_LEN // 4 + 2),
    ).compile()
    mem = compiled.memory_analysis()
    # both accumulators are updated in place, not copied per batch
    assert mem.alias_size_in_bytes == 2 * CAP * 4
    assert _device_bytes(mem) < V5E_HBM_BYTES
    # no gather over the batch's bytes (one costs about 8 ns a byte on a
    # v5e, a compiler-tiled scan under 1 ms a batch); the compaction's
    # gathers of its edge_bound slots stay
    gathered = [math.prod(int(x) for x in dims.split(",") if x)
                for dims in re.findall(r"\[([\d,]*)\]\S* gather\(",
                                       compiled.as_text())]
    assert gathered and NB * BUF_LEN not in gathered, gathered


def test_staged_build_compiles_for_v5e(one_chip, no_compile_cache):
    """The default CSR build at scale-22 capacity."""
    edges = _spec(one_chip, (CAP,), jnp.int32)
    compiled = build.csr_staged.lower(edges, edges, None, V, rho=4).compile()
    assert _device_bytes(compiled.memory_analysis()) < V5E_HBM_BYTES


def test_staged_build_has_no_search_for_v5e(one_chip, no_compile_cache):
    """The build at 2^24 edges, 2^20 vertices, rho 4 takes each edge's
    slot from the partition degree scan, with no binary search (a
    ``while`` loop over V + 1 queries).  XLA keeps copy loops of its
    own, so this looks for the op name, not for ``while``."""
    edges = _spec(one_chip, (1 << 24,), jnp.int32)
    compiled = build.csr_staged.lower(edges, edges, None, 1 << 20,
                                      rho=4).compile()
    text = compiled.as_text()
    assert "HloModule jit_csr_staged" in text
    assert "searchsorted" not in text


def test_mesh_exchange_build_compiles_for_v5e_2x2(topo, no_compile_cache):
    """The sharded load's exchange+build program over the four chips, at
    a Graph500 scale-22 text load's geometry: 992 blocks a shard, 2^20
    rows a chip, ``send_cap`` 3 * 2^21 and ``edge_limit`` 3 * 2^23 (the
    ``_cap_round`` steps above E/16 edges a bucket and E/4 a shard)."""
    d = 4
    mesh = Mesh(np.array(topo.devices[:d]), ("data",))
    e_per = 992 * (BUF_LEN // 4 + 2)
    fn = distributed._exchange_build_fn(mesh, "data", d, V // d, 3 << 21,
                                        False, 3 << 23)
    edges = jax.ShapeDtypeStruct((d * e_per,), jnp.int32,
                                 sharding=NamedSharding(mesh, P("data")))
    no_w = jax.ShapeDtypeStruct((), jnp.float32,
                                sharding=NamedSharding(mesh, P()))
    compiled = fn.lower(edges, edges, no_w).compile()
    text = compiled.as_text()
    assert "HloModule jit_exchange_build" in text
    assert " all-to-all(" in text
    assert "searchsorted" not in text           # the local build scans
    assert _device_bytes(compiled.memory_analysis()) < V5E_HBM_BYTES


@pytest.mark.parametrize("nb", [
    pytest.param(1, marks=pytest.mark.xfail(
        strict=True, raises=NotImplementedError,
        reason="Mosaic has no cumsum lowering")),
    pytest.param(NB, marks=pytest.mark.xfail(
        strict=True, raises=ValueError,
        reason="block shape (1, buf_len) is not (8, 128)-tiled")),
])
def test_pallas_parse_kernel_lowers_for_v5e(one_chip, no_compile_cache, nb):
    """The Pallas parse kernel does not lower for TPU yet; this turns
    into a pass (and a strict-xfail failure) once it does."""
    parse_bytes_kernel.lower(
        _spec(one_chip, (nb, BUF_LEN), jnp.uint8),
        _spec(one_chip, (2,), jnp.int32),
        weighted=False, base=1, interpret=False).compile()
