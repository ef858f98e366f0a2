"""GPT-NeoX family: rotary correctness, HF parity, MoE training."""
import numpy as np
import pytest

import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models.gptneox import GPTNeoXForCausalLM, gptneox_config

from . import reference_compare as compare
from .simple_model import seeded_params, token_batch


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def test_rotary_preserves_norm_and_relative_phase():
    from deepspeed_tpu.ops.rotary import apply_rotary_pos_emb

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    pos = jnp.arange(8)[None, :]
    qr, kr = apply_rotary_pos_emb(q, k, pos, rotary_dim=16)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(qr), axis=-1),
                               np.linalg.norm(np.asarray(q), axis=-1), rtol=1e-5)
    # relative property: <q_i, k_j> depends only on i-j
    def dots(qr, kr):
        return np.einsum("bshd,bthd->bhst", np.asarray(qr), np.asarray(kr))

    d = dots(qr, kr)
    qr2, kr2 = apply_rotary_pos_emb(q, k, pos + 5, rotary_dim=16)
    d2 = dots(qr2, kr2)
    np.testing.assert_allclose(d, d2, rtol=1e-4, atol=1e-5)


def test_neox_trains_zero3():
    model = GPTNeoXForCausalLM(gptneox_config("neox-tiny"))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3}})
    engine.init_params()
    batch = token_batch(engine.train_batch_size, 32, 512)
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_neox_moe_trains():
    from deepspeed_tpu.parallel.moe import MoEConfig

    model = GPTNeoXForCausalLM(gptneox_config(
        "neox-tiny", moe=MoEConfig(num_experts=4, capacity_factor=2.0)))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "mesh": {"ep": 4, "dp": 2}})
    engine.init_params()
    batch = token_batch(engine.train_batch_size, 32, 512)
    loss = float(engine.train_batch(batch))
    assert np.isfinite(loss)


def test_hf_gptneox_parity():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=64, rotary_pct=0.25,
        use_parallel_residual=True, hidden_act="gelu",
        attention_dropout=0.0, hidden_dropout=0.0)
    hf_model = transformers.GPTNeoXForCausalLM(hf_cfg).eval()

    from deepspeed_tpu.module_inject import convert_hf_model

    model, params = convert_hf_model(hf_model, dtype=jnp.float32)
    ids = np.random.default_rng(1).integers(0, 128, size=(2, 10))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = compare.apply(model, params, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours["logits"][:, :, :128], np.float32),
                               hf_logits, rtol=2e-3, atol=2e-3)


def test_neox_generate():
    cfg = gptneox_config("neox-tiny", dtype=jnp.float32)
    model = GPTNeoXForCausalLM(cfg)
    params = seeded_params(model)
    eng = deepspeed_tpu.init_inference(model=model, params=params,
                                      dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, 512, size=(1, 4)).astype(np.int32)
    out = np.asarray(eng.generate(ids, max_new_tokens=6))
    assert out.shape == (1, 10)
    # cached decode == full forward argmax; prompt tokens aren't generated,
    # so only the final generated token is comparable
    full = np.asarray(eng(out[:, :-1]), np.float32)
    assert int(out[0, -1]) == int(full.argmax(-1)[0, -1])
