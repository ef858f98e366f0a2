"""Parity tests for the fused Pallas op set (fused_mlp / decode_attention)
vs jnp references — the analog of the
reference's ``test_cuda_forward.py``/``test_cuda_backward.py`` kernel-parity
suite (values AND gradients), run in interpret mode on CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.decode_attention import decode_attention


def test_decode_attention_matches_masked_reference():
    rng = np.random.default_rng(3)
    B, S, H, D = 2, 32, 4, 64
    L = 13  # live prefix length (cache slots 0..12 valid)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

    out = decode_attention(q, k, v, L, interpret=True)

    scale = D ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(jnp.arange(S)[None, None, None, :] < L, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_fused_mlp_fwd_bwd_parity():
    from deepspeed_tpu.ops.pallas.fused_mlp import fused_mlp

    rng = np.random.default_rng(4)
    R, E, F = 96, 64, 256   # odd row count vs block 256 exercises padding
    x = jnp.asarray(rng.normal(size=(R, E)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(E, F)) * 0.05, jnp.float32)
    b1 = jnp.asarray(rng.normal(size=(F,)) * 0.05, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(F, E)) * 0.05, jnp.float32)
    b2 = jnp.asarray(rng.normal(size=(E,)) * 0.05, jnp.float32)

    def ref(x, w1, b1, w2, b2):
        return jax.nn.gelu(x @ w1 + b1, approximate=True) @ w2 + b2

    y = fused_mlp(x, w1, b1, w2, b2, block_rows=32, interpret=True)
    np.testing.assert_allclose(y, ref(x, w1, b1, w2, b2), rtol=2e-5, atol=2e-5)

    def loss_f(fn):
        return lambda *a: (fn(*a) ** 2).sum()

    gp = jax.jit(jax.grad(
        loss_f(lambda *a: fused_mlp(*a, block_rows=32, interpret=True)),
        argnums=(0, 1, 2, 3, 4)))(x, w1, b1, w2, b2)
    gr = jax.jit(jax.grad(loss_f(ref), argnums=(0, 1, 2, 3, 4)))(
        x, w1, b1, w2, b2)
    for a, r in zip(gp, gr):
        np.testing.assert_allclose(a, r, rtol=3e-4, atol=3e-4)


def test_fused_mlp_multi_tile_accumulation():
    """dw/db must sum over ALL row tiles (grid accumulation across programs)."""
    from deepspeed_tpu.ops.pallas.fused_mlp import fused_mlp

    rng = np.random.default_rng(5)
    R, E, F = 128, 32, 64
    x = jnp.asarray(rng.normal(size=(R, E)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(E, F)) * 0.1, jnp.float32)
    b1 = jnp.zeros((F,), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(F, E)) * 0.1, jnp.float32)
    b2 = jnp.zeros((E,), jnp.float32)

    def ref(x, w1, b1, w2, b2):
        return jax.nn.gelu(x @ w1 + b1, approximate=True) @ w2 + b2

    # block 16 → 8 tiles
    gp = jax.jit(jax.grad(
        lambda *a: fused_mlp(*a, block_rows=16, interpret=True).sum(),
        argnums=(1, 3)))(x, w1, b1, w2, b2)
    gr = jax.jit(jax.grad(lambda *a: ref(*a).sum(), argnums=(1, 3)))(
        x, w1, b1, w2, b2)
    np.testing.assert_allclose(gp[0], gr[0], rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(gp[1], gr[1], rtol=3e-4, atol=3e-4)


def test_fused_mlp_multi_f_tile(monkeypatch):
    """Force F // block_f > 1 (the dx-accumulation-over-f path) by
    shrinking the VMEM budget; grads must still match the reference."""
    from deepspeed_tpu.ops.pallas import fused_mlp as fm

    monkeypatch.setattr(fm, "_BWD_VMEM_BUDGET", 2 * 32 * 128 * 6 + 1)
    rng = np.random.default_rng(6)
    R, E, F = 64, 32, 512   # budget forces block_f=128 -> nf=4
    x = jnp.asarray(rng.normal(size=(R, E)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(E, F)) * 0.1, jnp.float32)
    b1 = jnp.asarray(rng.normal(size=(F,)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(F, E)) * 0.1, jnp.float32)
    b2 = jnp.zeros((E,), jnp.float32)
    assert fm._pick_block_f(E, F, 4) < F

    def ref(x, w1, b1, w2, b2):
        return jax.nn.gelu(x @ w1 + b1, approximate=True) @ w2 + b2

    gp = jax.jit(jax.grad(lambda *a: (fm.fused_mlp(*a, block_rows=32,
                                                   interpret=True) ** 2).sum(),
                          argnums=(0, 1, 2, 3)))(x, w1, b1, w2, b2)
    gr = jax.jit(jax.grad(lambda *a: (ref(*a) ** 2).sum(),
                          argnums=(0, 1, 2, 3)))(x, w1, b1, w2, b2)
    for a, r in zip(gp, gr):
        np.testing.assert_allclose(a, r, rtol=3e-4, atol=3e-4)


def test_fused_mlp_spmd_on_mesh():
    """fused_mlp under shard_map on a dp mesh (interpret) matches XLA."""
    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.ops.pallas.fused_mlp import fused_mlp_spmd

    mesh_mod.set_mesh(None)
    mesh = mesh_mod.build_mesh({"dp": 4, "fsdp": 2})
    mesh_mod.set_mesh(mesh)
    try:
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(8, 16, 64)), jnp.float32)
        w1 = jnp.asarray(rng.normal(size=(64, 256)) * 0.05, jnp.float32)
        b1 = jnp.zeros((256,), jnp.float32)
        w2 = jnp.asarray(rng.normal(size=(256, 64)) * 0.05, jnp.float32)
        b2 = jnp.zeros((64,), jnp.float32)
        y = fused_mlp_spmd(x, w1, b1, w2, b2, block_rows=16, interpret=True)
        assert y is not None
        ref = jax.nn.gelu(x @ w1 + b1, approximate=True) @ w2 + b2
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        # tp mesh -> refuses (hidden dim sharded)
        mesh_mod.set_mesh(None)
        mesh_mod.set_mesh(mesh_mod.build_mesh({"tp": 2, "dp": -1}))
        assert fused_mlp_spmd(x, w1, b1, w2, b2, interpret=True) is None
    finally:
        mesh_mod.set_mesh(None)


def test_decode_attention_gqa_matches_repeated_reference():
    """GQA decode: KV cache holds fewer heads; q head h reads KV head
    h // (H/KV).  Must equal the repeat-then-attend reference."""
    rng = np.random.default_rng(5)
    B, S, H, KV, D = 2, 32, 8, 2, 64
    L = 17
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32)

    out = decode_attention(q, k, v, L, interpret=True)

    rep = H // KV
    k_rep = jnp.repeat(k, rep, axis=2)
    v_rep = jnp.repeat(v, rep, axis=2)
    ref = decode_attention(q, k_rep, v_rep, L, interpret=True)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    # per-row lengths with GQA shapes
    lengths = jnp.asarray([5, 29])
    out_rows = decode_attention(q, k, v, lengths, interpret=True)
    ref_rows = decode_attention(q, k_rep, v_rep, lengths, interpret=True)
    np.testing.assert_allclose(out_rows, ref_rows, rtol=1e-5, atol=1e-5)

    # vmapped (continuous-batching) dispatch with GQA shapes
    out_v = jax.vmap(lambda qq, kk, vv, ll: decode_attention(
        qq, kk, vv, ll, interpret=True))(
        q[:, None], k[:, None], v[:, None], lengths[:, None])
    np.testing.assert_allclose(out_v[:, 0], out_rows, rtol=1e-5, atol=1e-5)

    import pytest as _pytest
    with _pytest.raises(ValueError):
        decode_attention(q, k[:, :, [0, 0, 0]], v[:, :, [0, 0, 0]], L,
                         interpret=True)  # KV=3 does not divide H=8


def test_decode_attention_blocked_long_context():
    """Caches too large for a single VMEM panel stream in KV blocks
    (flash-decode): the blocked path must match the single-panel math,
    including GQA shapes, per-row lengths, and the length edge cases."""
    from deepspeed_tpu.ops.pallas.decode_attention import (decode_supported,
                                                           fits_vmem)

    rng = np.random.default_rng(7)
    B, S, H, KV, D = 2, 8192, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32)
    # fp32 4096x2x64 panels exceed the VMEM budget → blocked path
    assert not fits_vmem(S, KV, D, 4)
    assert decode_supported(S, KV, D, 4)

    lengths = jnp.asarray([5000, 7])   # spans multiple blocks / first block
    out = decode_attention(q, k, v, lengths, interpret=True)

    rep = H // KV
    k_rep = jnp.repeat(k, rep, axis=2)
    v_rep = jnp.repeat(v, rep, axis=2)
    scale = D ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_rep) * scale
    live = jnp.arange(S)[None, None, None, :] < lengths[:, None, None, None]
    s = jnp.where(live, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", p, v_rep)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    # length exactly on a block boundary
    out_b = decode_attention(q, k, v, 1024, interpret=True)
    s2 = jnp.where(jnp.arange(S)[None, None, None, :] < 1024,
                   jnp.einsum("bqhd,bkhd->bhqk", q, k_rep) * scale, -jnp.inf)
    ref_b = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s2, -1), v_rep)
    np.testing.assert_allclose(out_b, ref_b, rtol=1e-5, atol=1e-5)


def test_decode_attention_blocked_ragged_tail(monkeypatch):
    """S not a multiple of the block: the padded last block's garbage
    positions are masked by k_pos < L.  Budget shrunk so the blocked path
    engages at test scale."""
    import importlib

    da_mod = importlib.import_module(
        "deepspeed_tpu.ops.pallas.decode_attention")

    monkeypatch.setattr(da_mod, "_VMEM_BUDGET_BYTES", 300 * 1024)
    monkeypatch.setattr(da_mod, "_DECODE_BLOCK_S", 256)
    da_mod._decode_op.cache_clear()   # dispatch depends on the budget
    try:
        rng = np.random.default_rng(9)
        B, S, H, D = 2, 900, 4, 64    # ragged vs the 128 block
        assert not da_mod.fits_vmem(S, H, D, 4)
        assert da_mod._pick_block(S, H, D, 4) == 128  # 900 = 7x128 + 4
        q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        lengths = jnp.asarray([899, 120])
        out = decode_attention(q, k, v, lengths, interpret=True)

        scale = D ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        live = jnp.arange(S)[None, None, None, :] < lengths[:, None, None, None]
        ref = jnp.einsum("bhqk,bkhd->bqhd",
                         jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1), v)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    finally:
        da_mod._decode_op.cache_clear()
