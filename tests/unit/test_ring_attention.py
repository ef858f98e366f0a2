"""Sequence-parallel attention vs dense reference — the SP subsystem has no
reference analog (SURVEY.md §2.2: v0.6.6 predates Ulysses/ring attention);
correctness oracle is dense attention on the gathered sequence."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from deepspeed_tpu.utils.compat import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.comm.mesh import build_mesh
from deepspeed_tpu.ops.attention import _jnp_attention
from deepspeed_tpu.parallel.ring_attention import ring_attention, ulysses_attention


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def _qkv(B=2, S=64, H=4, D=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    q, k, v = _qkv()
    mesh = build_mesh({"sp": 8})
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"))
    out = jax.jit(fn)(q, k, v)
    ref = _jnp_attention(q, k, v, causal=causal, bias=None, mask=None,
                         dropout_rate=0.0, dropout_rng=None, scale=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_dense(causal):
    q, k, v = _qkv(H=8)
    mesh = build_mesh({"sp": 4})
    fn = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"))
    out = jax.jit(fn)(q, k, v)
    ref = _jnp_attention(q, k, v, causal=causal, bias=None, mask=None,
                         dropout_rate=0.0, dropout_rng=None, scale=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_ring_attention_grads_flow():
    q, k, v = _qkv(S=32)
    mesh = build_mesh({"sp": 4})
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=True),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"))

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    g = jax.jit(jax.grad(loss))(q, k, v)
    ref_loss = lambda q, k, v: jnp.sum(_jnp_attention(
        q, k, v, causal=True, bias=None, mask=None, dropout_rate=0.0,
        dropout_rng=None, scale=None) ** 2)
    g_ref = jax.jit(jax.grad(ref_loss))(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=2e-3, atol=2e-4)


def test_ring_flash_matches_full_attention():
    """Flash-engine ring (pallas blocks + lse merge) must equal full causal
    attention — values AND gradients, including the dlse backward path."""
    from functools import partial

    import numpy as np
    from deepspeed_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.ops.attention import _jnp_attention
    from deepspeed_tpu.parallel.ring_attention import ring_attention_flash

    mesh_mod.set_mesh(None)
    mesh = mesh_mod.build_mesh({"sp": 4})
    rng = np.random.default_rng(0)
    B, S, H, D = 2, 256, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

    mapped = shard_map(
        partial(ring_attention_flash, axis_name="sp", causal=True,
                interpret=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False)

    out = jax.jit(mapped)(q, k, v)
    ref = _jnp_attention(q, k, v, causal=True, bias=None, mask=None,
                         dropout_rate=0.0, dropout_rng=None, scale=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    g1 = jax.jit(jax.grad(lambda q, k, v: (mapped(q, k, v) ** 2).sum(),
                          argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(lambda q, k, v: (_jnp_attention(
        q, k, v, causal=True, bias=None, mask=None, dropout_rate=0.0,
        dropout_rng=None, scale=None) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    for a, r in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=3e-4, atol=3e-4)


def test_ring_flash_non_causal():
    """causal=False must attend bidirectionally (every block full)."""
    from functools import partial

    import numpy as np
    from deepspeed_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.parallel.ring_attention import ring_attention_flash

    mesh_mod.set_mesh(None)
    mesh = mesh_mod.build_mesh({"sp": 4})
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    mapped = shard_map(
        partial(ring_attention_flash, axis_name="sp", causal=False,
                interpret=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False)
    out = jax.jit(mapped)(q, k, v)
    ref = _jnp_attention(q, k, v, causal=False, bias=None, mask=None,
                         dropout_rate=0.0, dropout_rng=None, scale=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_sp_flash_spec_planning():
    """Dispatch planning for the flash ring engine when sp shares the mesh
    with other active axes."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.ops.attention import sp_flash_spec

    mesh_mod.set_mesh(None)
    mesh = mesh_mod.build_mesh({"dp": 2, "sp": 2, "tp": 2})
    assert sp_flash_spec(mesh, batch_size=4, heads=4) == \
        P(("dp",), "sp", "tp", None)
    assert sp_flash_spec(mesh, batch_size=4, heads=3) is None     # H % tp
    assert sp_flash_spec(mesh, batch_size=3, heads=4) is None     # B % dp
    mesh_mod.set_mesh(None)
    mesh = mesh_mod.build_mesh({"pp": 2, "sp": 4})
    assert sp_flash_spec(mesh, batch_size=4, heads=4) is None     # pp nesting
    mesh_mod.set_mesh(None)
    mesh = mesh_mod.build_mesh({"sp": 8})
    assert sp_flash_spec(mesh, batch_size=1, heads=2) == \
        P(None, "sp", None, None)


def test_ring_flash_with_dp_and_tp_axes():
    """Flash-engine ring under a FULL-manual shard_map with dp AND tp
    active alongside sp (the composition the dispatch now builds) must
    still equal full attention — values and gradients."""
    from functools import partial

    import numpy as np
    from deepspeed_tpu.utils.compat import shard_map

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.ops.attention import _jnp_attention, sp_flash_spec
    from deepspeed_tpu.parallel.ring_attention import ring_attention_flash

    mesh_mod.set_mesh(None)
    mesh = mesh_mod.build_mesh({"dp": 2, "sp": 2, "tp": 2})
    rng = np.random.default_rng(1)
    B, S, H, D = 2, 256, 4, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

    spec = sp_flash_spec(mesh, B, H)
    assert spec is not None
    mapped = shard_map(
        partial(ring_attention_flash, axis_name="sp", causal=True,
                interpret=True),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)

    out = jax.jit(mapped)(q, k, v)
    ref = _jnp_attention(q, k, v, causal=True, bias=None, mask=None,
                         dropout_rate=0.0, dropout_rng=None, scale=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    g1 = jax.jit(jax.grad(lambda q, k, v: (mapped(q, k, v) ** 2).sum(),
                          argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(lambda q, k, v: (_jnp_attention(
        q, k, v, causal=True, bias=None, mask=None, dropout_rate=0.0,
        dropout_rng=None, scale=None) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    for a, r in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=3e-4, atol=3e-4)


def test_ulysses_flash_with_dp_and_tp_axes():
    """Ulysses SP with the flash kernel as the full-sequence engine,
    under the full-manual composed-mesh specs the dispatch builds."""
    from functools import partial

    import numpy as np
    from deepspeed_tpu.utils.compat import shard_map

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.ops.attention import _jnp_attention, sp_flash_spec
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.parallel.ring_attention import ulysses_attention

    mesh_mod.set_mesh(None)
    mesh = mesh_mod.build_mesh({"dp": 2, "sp": 2, "tp": 2})
    rng = np.random.default_rng(2)
    B, S, H, D = 2, 256, 4, 64   # H divides sp*tp = 4
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

    spec = sp_flash_spec(mesh, B, H)
    mapped = shard_map(
        partial(ulysses_attention, axis_name="sp", causal=True,
                attend_fn=partial(flash_attention, interpret=True)),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)
    out = jax.jit(mapped)(q, k, v)
    ref = _jnp_attention(q, k, v, causal=True, bias=None, mask=None,
                         dropout_rate=0.0, dropout_rng=None, scale=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    mesh_mod.set_mesh(None)
