"""SDAR (block-diffusion training, PR 40) as a ``LlamaConfig`` against
``benchmark/reference/sdar.py`` on seeded weights at a small size: the
logits of both halves, the weighted loss and the gradient of every kind of
leaf under a GIVEN mask and given levels; each named fault of the reference
moving the loss; the noise stream (one key one draw, the next step
another, a resumed engine the same); ``t = 1`` masking everything and the
loss then a hand-built mean; the eight shares of an expert layer adding up
to the uncut layer; the counters; what is refused; and the older
configurations lowering to the parent's StableHLO, block, loss head and
flash kernels.
"""
import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.models import common
from deepspeed_tpu.models.llama import (BlockDiffusionConfig, LlamaBlock,
                                        LlamaConfig, LlamaForCausalLM)
from deepspeed_tpu.parallel.moe import MoEConfig, MoELayer
from deepspeed_tpu.telemetry import get_registry

from . import reference_compare as compare

reference = load_module(ROOT, "reference", "sdar")

L, G, VOCAB, MASK_ID, ROUTED, HELD, TOP_K, EPS = 32, 4, 500, 499, 8, 4, 2, 1e-6
AUX = 0.01


def _config(first=2, held=HELD, g=G, **kw):
    moe = MoEConfig(num_experts=held, top_k=TOP_K, drop_tokens=False,
                    norm_topk_prob=True, expert_act="swiglu",
                    aux_loss_weight=AUX,
                    routed_experts=None if held == ROUTED else ROUTED,
                    first_expert=first)
    base = dict(vocab_size=VOCAB, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                intermediate_size=999, moe_intermediate_size=32,
                max_position_embeddings=128, rms_norm_eps=EPS, rope_theta=1e6,
                qk_norm="head", moe=moe, scan_layers=False,
                dtype=jnp.float32, attn_impl="jnp", vocab_pad_multiple=128,
                diffusion={"block_length": g, "mask_token_id": MASK_ID})
    base.update(kw)
    return LlamaConfig(**base)


def _reference_kwargs(cfg):
    return dict(n_layer=cfg.num_hidden_layers, n_head=cfg.num_attention_heads,
                n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
                vocab_size=cfg.vocab_size, top_k=TOP_K, rope_theta=1e6,
                eps=EPS, block_length=cfg.diffusion.block_length,
                mask_token_id=MASK_ID, routed_experts=ROUTED,
                first_expert=cfg.moe.first_expert)


def _noise(rng, rows, g=G):
    t = jnp.asarray(rng.uniform(0.05, 1.0, (rows, L // g)), jnp.float32)
    mask = jnp.asarray(rng.random((rows, L)) < np.repeat(np.asarray(t), g, 1))
    return mask, t


@pytest.fixture(scope="module")
def setup():
    mesh_lib.set_mesh(None)
    cfg = _config()
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, MASK_ID, (2, L)), jnp.int32)
    params = compare.init(model, ids, labels=ids)
    # norms away from 1 and matrices large enough that every part shows
    params = jax.tree_util.tree_map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.PRNGKey(3), x.shape),
        params)
    mask, t = _noise(rng, 2)
    return cfg, model, ids, params, mask, t


@pytest.fixture(scope="module")
def program(setup):
    """``(out, grads)`` under the fixture's noise: the weighted loss and
    every gradient from one compiled program."""
    cfg, model, ids, params, mask, t = setup
    with jax.default_matmul_precision("highest"):
        return compare.forward_and_gradients(lambda p: model.apply(
            {"params": p}, ids, labels=ids, diffusion_mask=mask,
            diffusion_t=t), params)


def test_the_logits_of_both_halves_match_the_reference(setup):
    cfg, model, ids, params, mask, t = setup
    with jax.default_matmul_precision("highest"):
        got = compare.apply(model, params, ids, diffusion_mask=mask,
                            diffusion_t=t)["logits"]
    want = reference.logits(params, ids, mask, **_reference_kwargs(cfg))
    assert got.shape == (2, 2 * L, cfg.padded_vocab_size)
    for half, a, b in zip(("noisy", "clean"), jnp.split(got, 2, 1),
                          jnp.split(want, 2, 1)):
        np.testing.assert_allclose(a[..., :VOCAB], b[..., :VOCAB], atol=2e-5,
                                   err_msg=half)
    # the two halves are different things: the clean half saw no mask
    assert float(jnp.abs(got[:, :L] - got[:, L:]).max()) > 0.1


@pytest.fixture(scope="module")
def losses(setup, program):
    cfg, model, ids, params, mask, t = setup
    want = reference.loss_and_grads(params, ids, mask, t, aux_loss_weight=AUX,
                                    **_reference_kwargs(cfg))
    return (program[0]["loss"], program[1]), want


def test_the_weighted_loss_matches_the_reference(setup, losses):
    (got, _), (want, _) = losses
    assert float(got) == pytest.approx(float(want), abs=2e-5)
    cfg, model, ids, params, mask, t = setup
    chunked = compare.apply(LlamaForCausalLM(_config(loss_chunk=16)), params,
                            ids, labels=ids, diffusion_mask=mask,
                            diffusion_t=t)
    assert "logits" not in chunked
    assert float(chunked["loss"]) == pytest.approx(float(want), abs=2e-4)


LEAVES = [("embed_tokens",), ("lm_head",), ("norm", "scale"),
          ("layers_0", "input_norm", "scale"),
          ("layers_0", "self_attn", "q_proj_kernel"),
          ("layers_0", "self_attn", "k_proj_kernel"),
          ("layers_0", "self_attn", "v_proj_kernel"),
          ("layers_0", "self_attn", "o_proj_kernel"),
          ("layers_0", "self_attn", "q_norm", "scale"),
          ("layers_1", "self_attn", "k_norm", "scale"),
          ("layers_1", "post_attention_norm", "scale"),
          ("layers_0", "moe", "gate", "wg"),
          ("layers_1", "moe", "experts", "gate"),
          ("layers_1", "moe", "experts", "up"),
          ("layers_0", "moe", "experts", "down")]


@pytest.mark.parametrize("path", LEAVES, ids="/".join)
def test_the_gradient_of_a_leaf_matches_the_reference(losses, path):
    (_, got), (_, want) = losses
    for key in path:
        got, want = got[key], want[key]
    compare.compare_leaves({path: got}, {path: want}, tol=2e-5,
                           measure="norm")


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_named_fault_of_the_reference_moves_the_loss(setup, losses, fault):
    """What the cell's comparison has to refuse is refusable: each fault
    moves the reference's loss by far more than program and sound
    reference differ."""
    cfg, model, ids, params, mask, t = setup
    (got, _), (want, _) = losses
    wrong = reference.training_loss(params, ids, mask, t, aux_loss_weight=AUX,
                                    fault=fault, **_reference_kwargs(cfg))
    assert abs(float(wrong) - float(got)) > 100 * abs(float(got) - float(want))
    assert abs(float(wrong) - float(got)) > 5e-3


def test_the_reference_mask_is_the_programs(setup):
    from deepspeed_tpu.ops.attention import block_diffusion_mask

    for length, g in ((32, 4), (64, 16), (96, 32)):
        theirs = reference.attention_mask(length, g)
        assert (np.asarray(block_diffusion_mask(length, g)) == theirs).all()
        assert theirs.sum() == length * (length + g)


def test_one_key_draws_one_noise_and_the_next_step_another(setup):
    cfg, model, ids, params, _, _ = setup
    key = jax.random.PRNGKey(7)

    run = jax.jit(lambda k: dict(model.apply(
        {"params": params}, ids, labels=ids, rngs={"diffusion": k})))

    def step(k):
        out = run(k)
        return float(out["loss"]), int(out["stats"]["diffusion_masked"]), \
            float(out["stats"]["diffusion_t_mean"])

    assert step(key) == step(key)
    assert step(key) != step(jax.random.fold_in(key, 1))
    loss, masked, t_mean = step(key)
    assert 0 < masked < ids.size and 0.0 < t_mean <= 1.0
    out = run(key)
    assert int(out["stats"]["diffusion_masked"]) \
        + int(out["stats"]["diffusion_kept"]) == ids.size


def test_without_a_stream_the_noise_has_to_be_given(setup):
    cfg, model, ids, params, mask, t = setup
    with pytest.raises(ValueError, match="'diffusion' random stream"):
        model.apply({"params": params}, ids, labels=ids)
    with pytest.raises(ValueError, match="come together"):
        model.apply({"params": params}, ids, labels=ids, diffusion_mask=mask)
    with pytest.raises(NotImplementedError, match="neither labels"):
        model.apply({"params": params}, ids)
    with pytest.raises(NotImplementedError, match="position_ids"):
        model.apply({"params": params}, ids, labels=ids,
                    position_ids=jnp.arange(L)[None])


def test_t_one_masks_everything_and_the_loss_is_a_plain_mean(setup):
    """At t = 1 every token is masked with weight 1: the loss is the mean
    negative log-likelihood of the clean tokens read from the noisy half's
    logits at their own positions, built here by hand."""
    cfg, model, ids, params, _, _ = setup
    one = jnp.ones((2, L // G))
    drawn = jnp.ones((2, L), bool)      # what uniform() < 1 always gives
    out = compare.apply(model, params, ids, labels=ids,
                        diffusion_mask=drawn, diffusion_t=one)
    assert int(out["stats"]["diffusion_masked"]) == ids.size
    both_halves = jax.jit(lambda tokens: model.apply(
        {"params": params}, tokens, diffusion_mask=drawn,
        diffusion_t=one)["logits"])
    logits = both_halves(ids)[:, :L, :VOCAB]
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, ids[..., None], -1)[..., 0]
    assert float(out["loss"] - out["aux_loss"]) == pytest.approx(
        float(nll.mean()), abs=2e-5)
    # every noisy position embeds the mask id: no clean token is read there
    other = ids.at[:, -G:].set((ids[:, -G:] + 1) % MASK_ID)
    np.testing.assert_allclose(both_halves(other)[:, :L - G],
                               both_halves(ids)[:, :L - G], atol=1e-5)


def test_a_label_of_minus_100_takes_a_position_out(setup):
    cfg, model, ids, params, mask, t = setup
    labels = jnp.where(jnp.arange(L)[None] < 8, -100, ids)
    a = compare.apply(model, params, ids, labels=labels,
                      diffusion_mask=mask, diffusion_t=t)
    weight = (mask & (labels != -100)) / jnp.repeat(t, G, 1)
    logits = a["logits"][..., :VOCAB]
    assert logits.shape[1] == L         # the head saw the noisy half alone
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, ids[..., None], -1)[..., 0]
    assert float(a["loss"] - a["aux_loss"]) == pytest.approx(
        float((weight * nll).sum() / (2 * L)), abs=2e-5)


def test_the_weights_other_form_is_one_a_masked_token(setup):
    """``loss_weight="one"``: the masked tokens' plain sum over B L, no
    1 / t; same mask, same logits."""
    cfg, model, ids, params, mask, t = setup
    plain = LlamaForCausalLM(_config(diffusion={
        "block_length": G, "mask_token_id": MASK_ID, "loss_weight": "one"}))
    out = compare.apply(plain, params, ids, labels=ids,
                        diffusion_mask=mask, diffusion_t=t)
    logits = out["logits"][..., :VOCAB]
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, ids[..., None], -1)[..., 0]
    assert float(out["loss"] - out["aux_loss"]) == pytest.approx(
        float((mask * nll).sum() / (2 * L)), abs=2e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One chip's share is a term of the whole: the parts that eight shares
    of two experts give, router and renormalisation counted once, sum to
    the uncut reference's layer; program and reference agree on every
    share; every pair is multiplied somewhere exactly once."""
    import dataclasses

    M, I, R, k = 32, 16, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, M))
    full = MoEConfig(num_experts=R, top_k=k, drop_tokens=False,
                     norm_topk_prob=True, expert_act="swiglu")
    whole = MoELayer(full, model_dim=M, hidden_dim=I, dtype=jnp.float32)
    p = compare.init(whole, x)
    p = {"gate": {"wg": p["gate"]["wg"] * 30},
         "experts": {n: w * 20 for n, w in p["experts"].items()}}
    uncut = reference.expert_ffn(p, x, top_k=k, first_expert=0)
    np.testing.assert_allclose(whole.apply({"params": p}, x)[0], uncut,
                               atol=2e-5)
    total, multiplied = 0.0, 0
    for first in range(0, R, 2):
        cfg = dataclasses.replace(full, num_experts=2, routed_experts=R,
                                  first_expert=first)
        mine = {"gate": p["gate"], "experts": {
            n: w[first:first + 2] for n, w in p["experts"].items()}}
        part, _, stats = MoELayer(cfg, model_dim=M, hidden_dim=I,
                                  dtype=jnp.float32).apply(
            {"params": mine}, x, return_stats=True)
        np.testing.assert_allclose(
            part, reference.expert_ffn(mine, x, top_k=k, first_expert=first),
            atol=2e-5)
        assert int(stats["dropped"]) == 0
        held = int(stats["tokens_per_expert"][first:first + 2].sum())
        assert int(stats["elsewhere"]) == 128 * k - held
        total, multiplied = total + part, multiplied + held
    assert multiplied == 128 * k
    np.testing.assert_allclose(total, uncut, atol=5e-5)


def test_the_engine_folds_the_stream_and_a_resumed_run_redraws_it():
    """``train_batch`` through the engine's own rng: two engines of one
    seed read the same first loss and book the same masked count, the
    second step masks other tokens, and ``eval_batch`` takes the noise as
    given."""
    import deepspeed_tpu

    ids = np.random.default_rng(2).integers(0, MASK_ID, (8, L)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}

    def engine():
        mesh_lib.set_mesh(None)
        e, _, _, _ = deepspeed_tpu.initialize(
            model=LlamaForCausalLM(_config(loss_chunk=32)), config={
                "train_micro_batch_size_per_gpu": 1,
                "steps_per_print": 10**9, "seed": 11,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3}, "mesh": {"fsdp": -1}})
        e.init_params()
        return e

    def masked():
        entry = get_registry().snapshot()["diffusion_tokens_total"]
        return {s["labels"]["kind"]: s["value"] for s in entry["samples"]}

    first, second = engine(), engine()
    assert first.model.rng_streams == ("diffusion",)
    base = masked() if "diffusion_tokens_total" in get_registry().snapshot() \
        else {"masked": 0.0, "kept": 0.0}
    a1 = float(first.train_batch(batch=batch))
    first.drain_step_stats(wait=True)
    after_one = masked()
    b1 = float(second.train_batch(batch=batch))
    second.drain_step_stats(wait=True)
    after_two = masked()
    assert a1 == b1
    step_one = after_one["masked"] - base["masked"]
    assert after_two["masked"] - after_one["masked"] == step_one
    assert 0 < step_one < ids.size
    assert (after_one["masked"] + after_one["kept"]
            - base["masked"] - base["kept"]) == ids.size
    first.train_batch(batch=batch)
    first.drain_step_stats(wait=True)
    assert masked()["masked"] - after_two["masked"] != step_one
    snap = get_registry().snapshot()
    assert 0.0 < snap["diffusion_t_mean"]["samples"][0]["value"] <= 1.0
    rng = np.random.default_rng(5)
    mask, t = (np.asarray(x) for x in _noise(rng, 8))
    given = dict(batch, diffusion_mask=mask, diffusion_t=t)
    assert float(first.eval_batch(given)) == float(first.eval_batch(given))
    with pytest.raises(ValueError, match="'diffusion' random stream"):
        first.eval_batch(batch)


def test_the_section_is_a_dict_or_a_dataclass_and_what_is_refused():
    cfg = _config()
    assert cfg.diffusion == BlockDiffusionConfig(block_length=G,
                                                 mask_token_id=MASK_ID)
    assert hash(cfg) == hash(_config())
    assert _config(diffusion=BlockDiffusionConfig(16, 3)).diffusion.t_min == 1e-3
    with pytest.raises(ValueError, match="does not divide 128"):
        _config(g=24)
    with pytest.raises(ValueError, match="no id of a vocabulary"):
        _config(diffusion={"mask_token_id": VOCAB})
    with pytest.raises(ValueError, match="loss_weight"):
        _config(diffusion={"mask_token_id": 1, "loss_weight": "sqrt"})
    with pytest.raises(NotImplementedError, match="denoising a block"):
        _config(decode=True)
    with pytest.raises(NotImplementedError, match="sliding"):
        _config(layer_types=["sliding_attention"] * 2, sliding_window=8)
    with pytest.raises(NotImplementedError, match="multi-token"):
        _config(num_nextn_predict_layers=1)
    model = LlamaForCausalLM(_config())
    ids = jnp.zeros((1, 30), jnp.int32)
    with pytest.raises(ValueError, match="whole blocks"):
        model.init(jax.random.PRNGKey(0), ids, labels=ids)


def test_the_scopes_are_emitted_where_the_section_is_set(setup):
    cfg, model, ids, params, mask, t = setup
    text = jax.jit(lambda p: model.apply(
        {"params": p}, ids, labels=ids,
        rngs={"diffusion": jax.random.PRNGKey(0)})["loss"]).lower(
        params).as_text(debug_info=True)
    for scope in ("diffusion/noise", "diffusion/halves",
                  "self_attn_blockdiff"):
        assert scope in text, scope


# -- the older configurations' programs stand ---------------------------

# sha256 of the StableHLO (value_and_grad, bf16, small widths) of one block,
# of a two-layer model with its chunked loss head, of the chunked head alone
# and of the flash kernels in interpret mode, taken at the parent commit
# (2b3f471) with the functions below: a new field, operand or mask that
# leaks into an old path changes one.  The two flash pins were taken anew on
# PR 47's tree (parent 4f98df5), which changes every flash kernel by design:
# the forward's denominator a partial sum a lane, the backward's score tiles
# keys by queries
PARENT = {
    "block olmoe":
        "c5383225cfca851b7e812bf4da2e835179e0390246ada61e2601454196e7bb03",
    "model olmoe":
        "1744a25534d3cf673df16f6025a0d9c643f9cc4e1f8f8425b02d92176ed60991",
    "block mellum2":
        "ba98729836851f882765494243dd5f420442a5fa22e1a81c211656f043af7e2a",
    "model mellum2":
        "b8e6d11268aab82c6e66f9572421e23fd4c6ad9bfda15c45e964e2ea8cf468a8",
    "head": "ae02e7599077772b44fb9c559ea17680529f80ac2245884f1fdf3c8864b48f0a",
    "flash causal":
        "a21d84f3c51d9be6ff64ddb8626d7badd39544a5cac0ad948ea0d54d5a15d5a9",
    "flash window":
        "557c584d1be9f117133aed4f647b5a4f669632dc6e0c5b1129a58109cbaed86d",
}
_MOE = dict(num_experts=4, top_k=2, drop_tokens=False, expert_act="swiglu")


def _kind(name):
    if name == "olmoe":
        return dict(moe=MoEConfig(norm_topk_prob=False, aux_loss_weight=0.01,
                                  z_loss_weight=0.001, **_MOE), qk_norm=True)
    return dict(moe=MoEConfig(routed_experts=8, first_expert=4,
                              norm_topk_prob=True, **_MOE),
                layer_types=["sliding_attention"] * 2, sliding_window=16,
                moe_intermediate_size=24, num_key_value_heads=2,
                rope_parameters={"sliding_attention": {
                    "rope_type": "default", "rope_theta": 1e4}})


def _older(what):
    kind, _, name = what.partition(" ")
    if kind == "block":
        cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_hidden_layers=1,
                          num_attention_heads=4, head_dim=16,
                          intermediate_size=40, max_position_embeddings=32,
                          scan_layers=False, attn_impl="jnp", **_kind(name))
        block = LlamaBlock(cfg, True, *cfg.kinds[:1])
        x = jnp.ones((2, 32, 32), jnp.bfloat16)
        inputs = (jnp.arange(32)[None, :], None)
        params = meta.unbox(jax.eval_shape(lambda: block.init(
            jax.random.PRNGKey(0), x, inputs))["params"])
        return jax.jit(jax.value_and_grad(
            lambda p, x: block.apply({"params": p}, x, inputs)[0].astype(
                jnp.float32).sum())).lower(params, x).as_text()
    if kind == "model":
        cfg = LlamaConfig(vocab_size=100, hidden_size=32, num_hidden_layers=2,
                          num_attention_heads=4, head_dim=16,
                          intermediate_size=40, max_position_embeddings=32,
                          scan_layers=False, attn_impl="jnp", loss_chunk=16,
                          **_kind(name))
        model = LlamaForCausalLM(cfg)
        ids = jnp.zeros((2, 32), jnp.int32)
        params = meta.unbox(jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), ids, labels=ids))["params"])
        return jax.jit(jax.value_and_grad(lambda p: model.apply(
            {"params": p}, ids, labels=ids)["loss"])).lower(params).as_text()
    if kind == "head":
        h = jnp.ones((2, 32, 32), jnp.bfloat16)
        w = jnp.ones((128, 32), jnp.float32)
        t = jnp.zeros((2, 32), jnp.int32)
        return jax.jit(jax.value_and_grad(lambda h, w: common.chunked_lm_loss(
            h, w, t, vocab_size=100, padded_vocab_size=128, chunk=16,
            dtype=jnp.bfloat16), (0, 1))).lower(h, w).as_text()
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    window, H, KV, D = (None, 2, 2, 64) if name == "causal" \
        else (192, 2, 1, 128)
    q = jnp.ones((1, 512, H, D), jnp.bfloat16)
    k = jnp.ones((1, 512, KV, D), jnp.bfloat16)
    kw = {} if window is None else {"window": window}
    return jax.jit(jax.value_and_grad(lambda q, k, v: fa.flash_attention(
        q, k, v, interpret=True, block_q=128, block_k=128, **kw).astype(
        jnp.float32).sum(), (0, 1, 2))).lower(q, k, k).as_text()


@pytest.mark.parametrize("what", sorted(PARENT))
def test_an_older_program_lowers_to_the_parents_stablehlo(what):
    """Their compile-cache keys and ``op_name``s stand, and nothing of the
    objective's section is traced where it is not set."""
    mesh_lib.set_mesh(None)
    text = _older(what)
    for scope in ("diffusion", "blockdiff"):
        assert scope not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT[what]


def test_the_head_products_read_as_before():
    """``lm_head_products_total``: forward 3, backward 0 a traced rule,
    with the objective's weights as without."""
    def read():
        entry = get_registry().snapshot().get("lm_head_products_total")
        return {s["labels"]["pass"]: s["value"]
                for s in (entry or {"samples": []})["samples"]}

    model = LlamaForCausalLM(_config(loss_chunk=16))
    ids = jnp.zeros((2, L), jnp.int32)
    params = compare.init(model, ids, labels=ids)
    common._fused_ce.cache_clear()
    before = read()
    jax.make_jaxpr(jax.grad(lambda p: model.apply(
        {"params": p}, ids, labels=ids,
        rngs={"diffusion": jax.random.PRNGKey(0)})["loss"]))(params)
    after = read()
    assert after.get("forward", 0) - before.get("forward", 0) == 3
    assert after.get("backward", 0) == before.get("backward", 0)
