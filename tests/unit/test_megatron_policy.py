"""Megatron GPT-2 injection policy (reference ``replace_policy.py:203``
``MegatronLayerPolicy``): raw Megatron state dict → zoo model.

Validated by ROUND-TRIP: synthesize a Megatron-layout checkpoint from a
randomly-initialized zoo model (including the [H, 3, head_dim] QKV
interleave and (out, in) Linear layout), convert it back through the
policy, and require identical logits."""
import numpy as np
import pytest

import jax

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config
from deepspeed_tpu.module_inject.policies import convert_megatron_gpt2

from . import reference_compare as compare
from .simple_model import seeded_params


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def _zoo_to_megatron_sd(params, n_head, interleave=True):
    """Inverse of the policy: zoo tree → classic Megatron names/layouts."""
    E = params["wte"].shape[1]
    dh = E // n_head
    h = params["h"]
    L = h["ln_1"]["scale"].shape[0]
    sd = {
        "model.language_model.embedding.word_embeddings.weight":
            np.asarray(params["wte"]),
        "model.language_model.embedding.position_embeddings.weight":
            np.asarray(params["wpe"]),
        "model.language_model.transformer.final_layernorm.weight":
            np.asarray(params["ln_f"]["scale"]),
        "model.language_model.transformer.final_layernorm.bias":
            np.asarray(params["ln_f"]["bias"]),
    }
    for i in range(L):
        p = f"model.language_model.transformer.layers.{i}."
        sd[p + "input_layernorm.weight"] = np.asarray(h["ln_1"]["scale"][i])
        sd[p + "input_layernorm.bias"] = np.asarray(h["ln_1"]["bias"][i])
        sd[p + "post_attention_layernorm.weight"] = \
            np.asarray(h["ln_2"]["scale"][i])
        sd[p + "post_attention_layernorm.bias"] = \
            np.asarray(h["ln_2"]["bias"][i])
        w = np.asarray(h["attn"]["c_attn_kernel"][i]).T     # (3E, E)
        b = np.asarray(h["attn"]["c_attn_bias"][i])         # (3E,)
        if interleave:
            w = w.reshape(3, n_head, dh, E).transpose(1, 0, 2, 3) \
                 .reshape(3 * E, E)
            b = b.reshape(3, n_head, dh).transpose(1, 0, 2).reshape(3 * E)
        sd[p + "attention.query_key_value.weight"] = w
        sd[p + "attention.query_key_value.bias"] = b
        sd[p + "attention.dense.weight"] = \
            np.asarray(h["attn"]["c_proj_kernel"][i]).T
        sd[p + "attention.dense.bias"] = np.asarray(h["attn"]["c_proj_bias"][i])
        sd[p + "mlp.dense_h_to_4h.weight"] = \
            np.asarray(h["mlp"]["c_fc_kernel"][i]).T
        sd[p + "mlp.dense_h_to_4h.bias"] = np.asarray(h["mlp"]["c_fc_bias"][i])
        sd[p + "mlp.dense_4h_to_h.weight"] = \
            np.asarray(h["mlp"]["c_proj_kernel"][i]).T
        sd[p + "mlp.dense_4h_to_h.bias"] = \
            np.asarray(h["mlp"]["c_proj_bias"][i])
    return sd


@pytest.mark.parametrize("interleave", [True, False])
def test_megatron_policy_roundtrip(interleave):
    cfg = gpt2_config("gpt2-tiny", vocab_pad_multiple=1, scan_layers=True)
    model = GPT2LMHeadModel(cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, 16)).astype(np.int32)
    params = seeded_params(model)
    ref_logits = compare.apply(model, params, ids)["logits"]

    sd = _zoo_to_megatron_sd(params, cfg.n_head, interleave=interleave)
    model2, params2 = convert_megatron_gpt2(
        sd, n_head=cfg.n_head, interleaved_qkv=interleave)
    assert model2.cfg.n_layer == cfg.n_layer
    assert model2.cfg.vocab_size == cfg.vocab_size
    out = compare.apply(model2, params2, ids)["logits"]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_logits, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_megatron_policy_rejects_ragged_layers():
    cfg = gpt2_config("gpt2-tiny", vocab_pad_multiple=1)
    model = GPT2LMHeadModel(cfg)
    params = seeded_params(model)
    sd = _zoo_to_megatron_sd(params, cfg.n_head)
    sd = {k: v for k, v in sd.items() if ".layers.0." not in k
          or "input_layernorm" in k}   # drop most of layer 0
    with pytest.raises(KeyError):
        convert_megatron_gpt2(sd, n_head=cfg.n_head)
