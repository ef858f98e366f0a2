"""GPT-Neo and GPT-J families: local attention, interleaved rotary, HF parity.

Parity targets: reference ``module_inject/replace_policy.py:113``
(HFGPTNEOLayerPolicy) and ``:158`` (HFGPTJLayerPolicy).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models.gptj import GPTJForCausalLM, gptj_config
from deepspeed_tpu.models.gptneo import GPTNeoForCausalLM, gptneo_config

from . import reference_compare as compare
from .simple_model import seeded_params, token_batch


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def test_interleaved_rotary_matches_half_split_on_permuted_channels():
    """rotate_every_two is half-split rotation under a channel permutation
    that interleaves the two halves; both must preserve norms."""
    from deepspeed_tpu.ops.rotary import apply_rotary_pos_emb

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    pos = jnp.arange(8)[None, :]
    qi, ki = apply_rotary_pos_emb(q, k, pos, rotary_dim=16, interleaved=True)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(qi), axis=-1),
                               np.linalg.norm(np.asarray(q), axis=-1), rtol=1e-5)
    # permutation equivalence: grouping even channels then odd channels
    # turns interleaved pairs (2i, 2i+1) into half-split pairs (i, i+8)
    perm = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    qh, kh = apply_rotary_pos_emb(q[..., perm], k[..., perm], pos, rotary_dim=16)
    inv = np.argsort(perm)
    np.testing.assert_allclose(np.asarray(qi), np.asarray(qh[..., inv]),
                               rtol=1e-5, atol=1e-6)


def test_gptneo_local_attention_window():
    """A local layer must not attend beyond window_size tokens back."""
    cfg = gptneo_config("neo-tiny", num_layers=1, attention_types=("local",),
                        window_size=4, dtype=jnp.float32)
    model = GPTNeoForCausalLM(cfg)
    params = seeded_params(model)
    base = np.asarray(compare.apply(model, params, jnp.asarray(
        np.random.default_rng(0).integers(0, 512, (1, 32)), jnp.int32))["logits"])
    # perturbing a token >window back must not change the last position
    ids2 = np.random.default_rng(0).integers(0, 512, (1, 32))
    ids2[0, 5] = (ids2[0, 5] + 1) % 512
    out2 = np.asarray(compare.apply(model, params,
                                  jnp.asarray(ids2, jnp.int32))["logits"])
    np.testing.assert_allclose(base[0, -1], out2[0, -1], rtol=1e-5, atol=1e-5)
    assert not np.allclose(base[0, 6], out2[0, 6], rtol=1e-5, atol=1e-5)


def test_gptneo_trains_zero2():
    model = GPTNeoForCausalLM(gptneo_config("neo-tiny"))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2}})
    engine.init_params()
    batch = token_batch(engine.train_batch_size, 32, 512)
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_gptj_trains_zero3():
    model = GPTJForCausalLM(gptj_config("gptj-tiny"))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3}})
    engine.init_params()
    batch = token_batch(engine.train_batch_size, 32, 512)
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_hf_gptneo_parity():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.GPTNeoConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
        max_position_embeddings=64, window_size=8,
        attention_types=[[["global", "local"], 1]],
        attention_dropout=0.0, embed_dropout=0.0, resid_dropout=0.0)
    hf_model = transformers.GPTNeoForCausalLM(hf_cfg).eval()

    from deepspeed_tpu.module_inject import convert_hf_model

    model, params = convert_hf_model(hf_model, dtype=jnp.float32)
    assert model.cfg.layer_attention_types == ("global", "local")
    ids = np.random.default_rng(1).integers(0, 128, size=(2, 12))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = compare.apply(model, params, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours["logits"][:, :, :128], np.float32),
                               hf_logits, rtol=2e-3, atol=2e-3)


def test_hf_gptj_parity():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.GPTJConfig(
        vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=2,
        rotary_dim=8, attn_pdrop=0.0, embd_pdrop=0.0, resid_pdrop=0.0)
    hf_model = transformers.GPTJForCausalLM(hf_cfg).eval()

    from deepspeed_tpu.module_inject import convert_hf_model

    model, params = convert_hf_model(hf_model, dtype=jnp.float32)
    ids = np.random.default_rng(1).integers(0, 128, size=(2, 12))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = compare.apply(model, params, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours["logits"][:, :, :128], np.float32),
                               hf_logits, rtol=2e-3, atol=2e-3)


def test_gptj_generate():
    cfg = gptj_config("gptj-tiny", dtype=jnp.float32)
    model = GPTJForCausalLM(cfg)
    import flax.linen as nn

    params = seeded_params(model)
    eng = deepspeed_tpu.init_inference(model=model, params=params,
                                       dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, 512, size=(1, 4)).astype(np.int32)
    out = np.asarray(eng.generate(ids, max_new_tokens=6))
    assert out.shape == (1, 10)
    full = np.asarray(eng(out[:, :-1]), np.float32)
    assert int(out[0, -1]) == int(full.argmax(-1)[0, -1])
