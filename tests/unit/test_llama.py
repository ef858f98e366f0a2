"""LLaMA family: training, GQA, HF parity, generation."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models.llama import LlamaForCausalLM, llama_config

from . import reference_compare as compare
from .simple_model import seeded_params, token_batch


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def test_llama_trains_zero3_tp():
    model = LlamaForCausalLM(llama_config("llama-tiny"))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3},
        "mesh": {"tp": 2, "fsdp": 4}})
    engine.init_params()
    batch = token_batch(engine.train_batch_size, 32, 512)
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_hf_llama_parity():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=64,
        max_position_embeddings=64, attention_dropout=0.0)
    hf_model = transformers.LlamaForCausalLM(hf_cfg).eval()

    from deepspeed_tpu.module_inject import convert_hf_model

    model, params = convert_hf_model(hf_model, dtype=jnp.float32)
    ids = np.random.default_rng(1).integers(0, 128, size=(2, 10))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = compare.apply(model, params, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours["logits"][:, :, :128], np.float32),
                               hf_logits, rtol=2e-3, atol=2e-3)


def test_llama_generate_matches_forward():
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = seeded_params(model)
    eng = deepspeed_tpu.init_inference(model=model, params=params,
                                      dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, 512, size=(1, 4)).astype(np.int32)
    out = np.asarray(eng.generate(ids, max_new_tokens=6))
    assert out.shape == (1, 10)
    full = np.asarray(eng(out[:, :-1]), np.float32)
    assert int(out[0, -1]) == int(full.argmax(-1)[0, -1])


def test_llama_continuous_batcher_fp_and_int8():
    """The bench's llama GQA serving path: continuous batching over the
    grouped-query decode cache, fp and W8A16, with cache_len sized to
    the generation budget (max_tokens)."""
    from deepspeed_tpu.inference.serving import ContinuousBatcher

    rng = np.random.default_rng(0)
    for quant in ({}, {"enabled": True, "bits": 8}):
        mesh_mod.set_mesh(None)
        cfg = llama_config("llama-tiny")
        model = LlamaForCausalLM(cfg)
        params = seeded_params(model)
        eng = deepspeed_tpu.init_inference(model=model, params=params,
                                           quant=quant, max_tokens=32)
        # rotary family: max_tokens resizes the cache itself
        assert eng._gen_limit == 32
        cache_lens = {l.shape[-3] for p, l in
                      jax.tree_util.tree_leaves_with_path(eng.init_cache(1))
                      if "cached_key" in jax.tree_util.keystr(p)}
        assert cache_lens == {32}, cache_lens
        b = ContinuousBatcher(eng, n_slots=2)
        prompts = [rng.integers(0, cfg.vocab_size, (7,)).astype(np.int32)
                   for _ in range(4)]
        outs = b.run(prompts, max_new_tokens=9, ticks=4)
        assert all(len(o) == 16 for o in outs), [len(o) for o in outs]
