"""Weight-only int8 (W8A16) serving tests — real int8 storage + compute
(ops/w8.py; reference ``pt_binding.cpp:622`` int8 GEMM family)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config
from deepspeed_tpu.ops.w8 import quantize_weight, w8a16_matmul

from . import reference_compare as compare
from .simple_model import seeded_params


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def test_w8a16_matmul_matches_dense():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(256, 192)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)
    codes, scale = quantize_weight(w, group=64)
    assert codes.dtype == jnp.int8 and codes.shape == (256, 192)
    assert scale.shape == (4, 192)
    y_ref = x @ w
    y_q = w8a16_matmul(x, codes, scale)
    # int8 grouped quantization error is small relative to signal
    rel = float(jnp.linalg.norm(y_q - y_ref) / jnp.linalg.norm(y_ref))
    assert rel < 0.02, rel


def test_w8a16_stacked_layers():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(3, 128, 64)), jnp.float32)  # (L, K, N)
    codes, scale = quantize_weight(w, group=32)
    assert codes.shape == (3, 128, 64) and scale.shape == (3, 4, 64)
    y = w8a16_matmul(jnp.ones((2, 128)), codes[1], scale[1])
    ref = jnp.ones((2, 128)) @ w[1]
    assert float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref)) < 0.02


def test_init_inference_int8_real_storage():
    cfg = gpt2_config("gpt2-tiny")
    model = GPT2LMHeadModel(cfg)
    params = seeded_params(model)

    eng_fp = deepspeed_tpu.init_inference(model=model, params=params)
    mesh_mod.set_mesh(None)
    eng_q8 = deepspeed_tpu.init_inference(
        model=GPT2LMHeadModel(cfg), params=params,
        config={"quant": {"enabled": True, "bits": 8}})

    # storage really is int8: every dense kernel replaced by codes+scales
    leaves = jax.tree_util.tree_leaves_with_path(eng_q8.params)
    q_leaves = [(p, l) for p, l in leaves
                if jax.tree_util.keystr(p).endswith("_kernel_q']")]
    assert q_leaves and all(l.dtype == jnp.int8 for _, l in q_leaves)
    assert not any(jax.tree_util.keystr(p).endswith("_kernel']")
                   for p, _ in leaves)
    # kernel storage: int8 codes + scales ≤ ~60% of the fp kernels (the
    # fp engine itself now stores bf16 at load, so the bound is vs bf16;
    # codes are exactly half of bf16, scales add a sliver — at this tiny
    # size the group falls back to g=K so scales are one fp32 row)
    q8_kernel_bytes = sum(
        l.nbytes for p, l in leaves
        if "_kernel_q']" in jax.tree_util.keystr(p)
        or "_kernel_s']" in jax.tree_util.keystr(p))
    fp_kernel_bytes = sum(
        l.nbytes for p, l in
        jax.tree_util.tree_leaves_with_path(eng_fp.params)
        if jax.tree_util.keystr(p).endswith("_kernel']"))
    assert q8_kernel_bytes < 0.6 * fp_kernel_bytes

    # compute stays faithful: greedy decode agrees with full precision
    ids = np.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, size=(1, 16)),
        np.int32)
    logits_fp = np.asarray(jax.device_get(eng_fp(ids)), np.float32)
    logits_q8 = np.asarray(jax.device_get(eng_q8(ids)), np.float32)
    agree = np.mean(logits_fp.argmax(-1) == logits_q8.argmax(-1))
    assert agree > 0.9, agree
    out = eng_q8.generate(ids, max_new_tokens=8)
    assert out.shape == (1, 24)


def test_quant_bits4_keeps_fake_path():
    cfg = gpt2_config("gpt2-tiny")
    model = GPT2LMHeadModel(cfg)
    params = seeded_params(model)
    eng = deepspeed_tpu.init_inference(
        model=GPT2LMHeadModel(cfg), params=params,
        config={"quant": {"enabled": True, "bits": 4, "groups": 16}})
    # fake-quant path: structure unchanged (full-width leaves)
    assert any(jax.tree_util.keystr(p).endswith("_kernel']")
               for p, _ in jax.tree_util.tree_leaves_with_path(eng.params))


def test_llama_int8_serving():
    """W8A16 covers the LLaMA family too (GQA decode path)."""
    from deepspeed_tpu.models.llama import LlamaForCausalLM, llama_config

    cfg = llama_config("llama-tiny")
    model = LlamaForCausalLM(cfg)
    params = seeded_params(model)

    eng_fp = deepspeed_tpu.init_inference(
        model=LlamaForCausalLM(cfg), params=params)
    mesh_mod.set_mesh(None)
    eng_q8 = deepspeed_tpu.init_inference(
        model=LlamaForCausalLM(cfg), params=params,
        config={"quant": {"enabled": True, "bits": 8}})
    leaves = jax.tree_util.tree_leaves_with_path(eng_q8.params)
    assert any(jax.tree_util.keystr(p).endswith("_kernel_q']")
               and l.dtype == jnp.int8 for p, l in leaves)
    ids = np.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(1, 16)), np.int32)
    a = np.asarray(jax.device_get(eng_fp(ids)), np.float32)
    b = np.asarray(jax.device_get(eng_q8(ids)), np.float32)
    rel = np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-6)
    assert rel < 0.05, rel
    out = eng_q8.generate(ids, max_new_tokens=6)
    assert out.shape == (1, 22)


@pytest.mark.parametrize("family", ["gptj", "gptneo", "gptneox"])
def test_w8_serving_all_decoder_families(family):
    """Every decoder family shares the W8A16 path (declare_w8_dense)."""
    import importlib

    mod = importlib.import_module(f"deepspeed_tpu.models.{family}")
    cfg_fn = getattr(mod, f"{family}_config")
    cls = {"gptj": "GPTJForCausalLM", "gptneo": "GPTNeoForCausalLM",
           "gptneox": "GPTNeoXForCausalLM"}[family]
    Model = getattr(mod, cls)
    cfg = cfg_fn()  # tiny preset default
    params = seeded_params(Model(cfg))

    eng_fp = deepspeed_tpu.init_inference(model=Model(cfg), params=params)
    mesh_mod.set_mesh(None)
    eng_q8 = deepspeed_tpu.init_inference(
        model=Model(cfg), params=params,
        config={"quant": {"enabled": True, "bits": 8}})
    leaves = jax.tree_util.tree_leaves_with_path(eng_q8.params)
    assert any(jax.tree_util.keystr(p).endswith("_kernel_q']")
               and l.dtype == jnp.int8 for p, l in leaves)
    ids = np.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(1, 16)), np.int32)
    a = np.asarray(jax.device_get(eng_fp(ids)), np.float32)
    b = np.asarray(jax.device_get(eng_q8(ids)), np.float32)
    # untrained logits are near-uniform, so argmax flips under tiny quant
    # noise — compare the logit field itself
    rel = np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-6)
    assert rel < 0.05, rel
    out = eng_q8.generate(ids, max_new_tokens=4)
    assert out.shape == (1, 20)


def test_w8_bert_encoder_forward():
    """Encoder family: w8 cfg + quantize_dense_tree agree with fp."""
    from deepspeed_tpu.models.bert import BertModel, bert_config
    from deepspeed_tpu.ops.w8 import quantize_dense_tree
    import dataclasses

    cfg = bert_config("bert-tiny")
    model = BertModel(cfg)
    ids = np.zeros((1, 16), np.int32)
    params = seeded_params(model)
    out_fp = compare.apply(model, params, ids)
    q_model = BertModel(dataclasses.replace(cfg, w8=True))
    q_params = quantize_dense_tree(
        jax.tree_util.tree_map(np.asarray, params))
    out_q8 = compare.apply(q_model, q_params, ids)
    a = np.asarray(jax.tree_util.tree_leaves(out_fp)[0], np.float32)
    b = np.asarray(jax.tree_util.tree_leaves(out_q8)[0], np.float32)
    rel = np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-6)
    assert rel < 0.05, rel


def test_moe_expert_int8_serving():
    """MoE expert FFNs (wi/wo) join the int8 path; gate stays fp."""
    from deepspeed_tpu.parallel.moe import MoEConfig

    cfg = gpt2_config("gpt2-tiny", scan_layers=True,
                      moe=MoEConfig(num_experts=2, top_k=1,
                                    capacity_factor=2.0))
    model = GPT2LMHeadModel(cfg)
    params = seeded_params(model)

    eng_fp = deepspeed_tpu.init_inference(
        model=GPT2LMHeadModel(cfg), params=params)
    mesh_mod.set_mesh(None)
    eng_q8 = deepspeed_tpu.init_inference(
        model=GPT2LMHeadModel(cfg), params=params,
        config={"quant": {"enabled": True, "bits": 8}})

    leaves = dict(jax.tree_util.tree_leaves_with_path(eng_q8.params))
    paths = [jax.tree_util.keystr(p) for p in leaves]
    assert any(p.endswith("wi_q']") for p in paths), paths[:5]
    assert any(p.endswith("wo_q']") for p in paths)
    assert not any(p.endswith("'wi']") or p.endswith("'wo']")
                   for p in paths)
    assert any(p.endswith("'wg']") for p in paths)   # gate full width

    ids = np.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(1, 16)), np.int32)
    a = np.asarray(jax.device_get(eng_fp(ids)), np.float32)
    b = np.asarray(jax.device_get(eng_q8(ids)), np.float32)
    rel = np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-6)
    assert rel < 0.05, rel


def test_gptneox_moe_int8_serving():
    """NeoX MoE + int8: expert leaves quantize and the module consumes
    them (regression: MoELayer must receive the family's w8 flag)."""
    from deepspeed_tpu.models.gptneox import (GPTNeoXForCausalLM,
                                              gptneox_config)
    from deepspeed_tpu.parallel.moe import MoEConfig

    cfg = gptneox_config(moe=MoEConfig(num_experts=2, top_k=1,
                                       capacity_factor=2.0))
    model = GPTNeoXForCausalLM(cfg)
    params = seeded_params(model)
    eng = deepspeed_tpu.init_inference(
        model=GPTNeoXForCausalLM(cfg), params=params,
        config={"quant": {"enabled": True, "bits": 8}})
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(eng.params)]
    assert any(p.endswith("wi_q']") for p in paths)
    ids = np.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(1, 12)), np.int32)
    out = eng.generate(ids, max_new_tokens=4)
    assert out.shape == (1, 16)


def test_w8a16_pallas_kernel_matches_einsum():
    """Round-4 (VERDICT #4): the Pallas panel kernel must match the
    grouped-einsum dequant path, including the vmapped-slots fold."""
    import deepspeed_tpu.ops.pallas.w8_matmul as wm
    from deepspeed_tpu.ops.w8 import quantize_weight

    wm.INTERPRET = True
    try:
        rng = np.random.default_rng(5)
        K, N = 256, 384
        w = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
        codes, scale = quantize_weight(w, group=128)
        for M in (1, 7, 8):
            x = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
            deq = (codes.astype(jnp.float32).reshape(-1, 128, N)
                   * scale[:, None, :]).reshape(K, N)
            ref = x.astype(jnp.float32) @ deq
            got = wm.w8a16_matmul_pallas(x, codes, scale)
            assert got.shape == (M, N)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-2, atol=2e-2)
        # slot-vmapped calls fold into matmul rows (one panel stream)
        xv = jnp.asarray(rng.standard_normal((4, 1, K)), jnp.bfloat16)
        gv = jax.vmap(wm.w8a16_matmul_pallas,
                      in_axes=(0, None, None))(xv, codes, scale)
        for i in range(4):
            np.testing.assert_array_equal(
                np.asarray(gv[i]),
                np.asarray(wm.w8a16_matmul_pallas(xv[i], codes, scale)))
    finally:
        wm.INTERPRET = False


def test_w8a16_pallas_supported_guard():
    from deepspeed_tpu.ops.pallas.w8_matmul import supported

    assert supported((8, 256), (256, 384), 2, mesh_ok=True)
    assert not supported((8, 256), (256, 384), 2, mesh_ok=False)
    assert not supported((8, 200), (200, 384), 1, mesh_ok=True)  # K%128
    assert not supported((512, 256), (256, 384), 2, mesh_ok=True)  # M cap
