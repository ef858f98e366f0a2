"""Kernels of the main path compiled at real widths for a v5e chip that is
described, not attached (the on-chip-measurement guide, section 2): what
the TPU compiler would refuse on the chip (a tile that overflows VMEM, a
block off the tiling) it refuses here, at no chip time.  Nothing runs.

The topology is described inside a fixture and only in this file: the
TPU library loads in the one worker that is given these tests.
"""
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)])
def test_grouped_matmul_compiles_at_olmoe_widths(one_chip, k, n):
    """The expert matmuls of ``train-olmoe-z3-1chip``: 8192 tokens x top-8
    rows over 64 experts, forward and both backward kernels, at the tiles
    ``ops/grouped_matmul.py`` picks for the shape."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from deepspeed_tpu.ops.grouped_matmul import TILES, _tiles

    rows, experts = 8192 * 8, 64
    tiles = _tiles(rows, k, n)
    assert tiles == TILES

    def loss(lhs, rhs, sizes):
        return gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype,
                   tiling=tiles).astype(jnp.float32).sum()

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        arg((rows, k), jnp.bfloat16), arg((experts, k, n), jnp.bfloat16),
        arg((experts,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_sorted_dispatch_compiles_per_rank_on_four_chips(topo, monkeypatch):
    """``fsdp 4`` over the host's 2x2 chips, OLMoE's widths, 2 x 4096 tokens
    a chip: every rank runs the Pallas grouped matmuls on its own 65,536
    sorted rows inside a ``shard_map`` (nine kernels forward and backward),
    the ZeRO-sharded expert leaves are gathered for it and their gradients
    summed over the ranks; no XLA ragged dot is left in the program."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.parallel.moe import sorted_dispatch

    # the dispatch asks jax.devices() for the platform and would see the CPU
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 4, 1, 1, 1),
                mesh_mod.MESH_AXES)
    monkeypatch.setattr(mesh_mod, "_CURRENT_MESH", mesh)
    tokens, k, experts, embed, mlp = 4 * 8192, 8, 64, 2048, 1024

    def arg(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    def loss(x, weights, chosen, *ws):
        return sorted_dispatch(x, weights, chosen, ws, "swiglu"
                               ).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 3, 4, 5))).lower(
        arg((tokens, embed), jnp.bfloat16, "fsdp"),
        arg((tokens, k), jnp.float32, "fsdp"),
        arg((tokens, k), jnp.int32, "fsdp"),
        arg((experts, embed, mlp), jnp.bfloat16, None, "fsdp"),
        arg((experts, embed, mlp), jnp.bfloat16, None, "fsdp"),
        arg((experts, mlp, embed), jnp.bfloat16, None, None, "fsdp"),
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 9
    assert "ragged" not in text
    assert "all-gather" in text and ("reduce-scatter" in text
                                     or "all-reduce" in text)
