"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU v5e.

The TPU compiler is installed with jax, and it compiles for a chip that is
described rather than attached, so these tests catch what interpret mode
cannot: block shapes the chip's tiling refuses, and kernels that overrun
its fast memory.  Shapes are the default cascade's published widths:
gemma3-1b (KV 1, G 4, head 256, vocab 262144) and phi4-mini-3.8b (KV 8,
G 3, head 128, vocab 200064), 8 rows of a 528-token paged arena in
16-token blocks, bfloat16 as the engine serves them.

The topology is described inside a fixture, so only the worker that runs
this file loads the TPU library; nothing here runs a kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from bench.harness import tracered
from repro.kernels import ops
from repro.kernels.confidence_gate import confidence_gate
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ragged_attention import ragged_attention

SLOTS, BLOCK, PAGES = 8, 16, 33
BLOCKS = SLOTS * PAGES + 1
# (tier, kv heads, queries per kv head, head dim, sliding window)
HEADS = [("gemma3-1b", 1, 4, 256, 512), ("phi4-mini-3.8b", 8, 3, 128, None)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernels_named(compiled):
    """What the benchmark's trace reduction calls the program's Mosaic
    kernels: ``tracered.op_key`` of each kernel instruction's name, which
    is the name a device trace gives the kernel's events."""
    return {tracered.op_key(line.split(" = ", 1)[0].split()[-1], {})
            for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line}


def _compile(fn, sharding, *shapes, kernel=None):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()      # the Mosaic kernel
    if kernel is not None:
        assert kernel in _kernels_named(compiled)
    return compiled


@pytest.mark.parametrize("vocab", [262144, 200064])
@pytest.mark.parametrize("rows", [8, 512])
def test_confidence_gate_compiles(one_chip, vocab, rows):
    """Rows past one 8-row tile were refused before the blocks were 2-D."""
    _compile(lambda x: confidence_gate(x, interpret=False), one_chip,
             ((rows, vocab), jnp.bfloat16), kernel="confidence_gate")


@pytest.mark.parametrize("tier,kv,g,hd,window", HEADS)
def test_ragged_attention_compiles(one_chip, tier, kv, g, hd, window):
    width = 512
    _compile(lambda q, k, v, pt, qs, ql: ragged_attention(
                 q, k, v, pt, qs, ql, window=window, interpret=False),
             one_chip,
             ((width, kv, g, hd), jnp.bfloat16),
             ((BLOCKS, BLOCK, kv, hd), jnp.bfloat16),
             ((BLOCKS, BLOCK, kv, hd), jnp.bfloat16),
             ((SLOTS, PAGES), jnp.int32), ((SLOTS,), jnp.int32),
             ((SLOTS,), jnp.int32), kernel="ragged_attention")


# The benchmark's chat cell: phi4-mini-3.8b -> starcoder2-7b (KV 4, G 9,
# head 128), 18 rows of 72 pages, 1297 blocks of 16, at its smallest and
# largest flat widths.
CELL_SLOTS, CELL_PAGES, CELL_BLOCKS = 18, 72, 1297
CELL_HEADS = [("phi4-mini-3.8b", 8, 3, 128), ("starcoder2-7b", 4, 9, 128)]


@pytest.mark.parametrize("width", [16, 1152])
@pytest.mark.parametrize("tier,kv,g,hd", CELL_HEADS)
def test_ragged_attention_compiles_at_the_chat_cells_shapes(
        one_chip, tier, kv, g, hd, width):
    """The page-group double buffers fit the chip's VMEM at the cell's
    widths, and the kernel keeps the name the benchmark's trace
    reduction reads its share and roofline under."""
    _compile(lambda q, k, v, pt, qs, ql: ragged_attention(
                 q, k, v, pt, qs, ql, interpret=False),
             one_chip,
             ((width, kv, g, hd), jnp.bfloat16),
             ((CELL_BLOCKS, BLOCK, kv, hd), jnp.bfloat16),
             ((CELL_BLOCKS, BLOCK, kv, hd), jnp.bfloat16),
             ((CELL_SLOTS, CELL_PAGES), jnp.int32),
             ((CELL_SLOTS,), jnp.int32), ((CELL_SLOTS,), jnp.int32),
             kernel="ragged_attention")


@pytest.mark.parametrize("tier,kv,g,hd,window", HEADS)
def test_paged_attention_compiles(one_chip, tier, kv, g, hd, window):
    """phi4's KV=8 was refused while each block held one KV head."""
    _compile(lambda q, k, v, pt, pos: paged_attention(
                 q, k, v, pt, pos, window=window, interpret=False),
             one_chip,
             ((SLOTS, kv, g, hd), jnp.bfloat16),
             ((BLOCKS, BLOCK, kv, hd), jnp.bfloat16),
             ((BLOCKS, BLOCK, kv, hd), jnp.bfloat16),
             ((SLOTS, PAGES), jnp.int32), ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("tier,kv,g,hd,window", HEADS)
def test_ragged_attention_compiles_on_a_tier_mesh(topo, tier, kv, g, hd,
                                                  window):
    """A tier sharded over two chips (``--tier-mesh 2x1``): the compiler
    cannot partition a Mosaic kernel, so ``ops.ragged_attention`` must run
    it per data shard inside ``shard_map``."""
    mesh = Mesh(np.asarray(topo.devices[:2]).reshape(2, 1), ("data", "model"))
    rep, shard = PartitionSpec(), PartitionSpec("data")

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    blocks = BLOCKS + 1                     # rounded up to divide 2 shards
    args = [arg((512, kv, g, hd), jnp.bfloat16, rep),
            arg((blocks, BLOCK, kv, hd), jnp.bfloat16, shard),
            arg((blocks, BLOCK, kv, hd), jnp.bfloat16, shard),
            arg((SLOTS, PAGES), jnp.int32, shard),
            arg((SLOTS,), jnp.int32, shard), arg((SLOTS,), jnp.int32, shard)]
    with jax.set_mesh(mesh):
        compiled = jax.jit(lambda *a: ops.ragged_attention(
            *a, window=window, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "ragged_attention" in _kernels_named(compiled)
