"""OLMoE through the program (``LlamaConfig`` + dropless ``MoEConfig``)
against the plain reference ``benchmark/reference/olmoe.py``, on seeded
random weights, CPU, float32 compute, at tiny widths that keep the shape of
the thing: 16 experts, top-8, 2 layers, 4 heads, QK-norm, untied head.

TOL = 2e-5: both sides compute in float32 on the CPU and differ only in
the order of sums (sorted grouped matmul against a masked loop over
experts, chunked against full head).  Measured here: logits differ by at
most 3.7e-7, gradients by 8.6e-8, the loss by 1.4e-6 (the chunked head).
A router matrix rounded to bf16 moves a logit by 2.7e-4 and an expert whose
pairs are dropped by far more (test_bf16_router_or_dropped_pair...): both
fail the tolerance by more than ten times.
"""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.parallel.moe import MoEConfig, MoELayer, topk_routing

from . import reference_compare as compare

TOL = 2e-5
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
V, E, L, H, I, S = 500, 64, 2, 4, 32, 48
N_EXP, TOP_K = 16, 8


def _reference():
    spec = importlib.util.spec_from_file_location(
        "olmoe_reference", os.path.join(ROOT, "benchmark", "reference", "olmoe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def build(norm_topk_prob=False, scan=False, loss_chunk=0, **moe_kw):
    moe = MoEConfig(num_experts=N_EXP, top_k=TOP_K, drop_tokens=False,
                    aux_loss_weight=0.01, z_loss_weight=0.001,
                    norm_topk_prob=norm_topk_prob, expert_act="swiglu",
                    **moe_kw)
    cfg = LlamaConfig(vocab_size=V, hidden_size=E, num_hidden_layers=L,
                      num_attention_heads=H, intermediate_size=I,
                      max_position_embeddings=S, moe=moe, qk_norm=True,
                      loss_chunk=loss_chunk, scan_layers=scan,
                      dtype=jnp.float32, attn_impl="jnp")
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(7)
    ids = jnp.asarray(rng.integers(0, V, (2, S)), jnp.int32)
    params = compare.init(model, ids, seed=3)
    # random norm scales, so a norm that is missing or misplaced shows
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(11), len(leaves))
    new = []
    for (path, leaf), k in zip(leaves, keys):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if name.endswith("scale"):
            leaf = 1.0 + 0.2 * jax.random.normal(k, leaf.shape, leaf.dtype)
        elif "experts" in name or "proj" in name or name.endswith("wg"):
            leaf = leaf * 5.0        # 0.02-init FFNs barely move a logit
        new.append(leaf)
    return model, cfg, jax.tree_util.tree_unflatten(tree, new), ids


def ref_kw(cfg):
    return dict(n_layer=L, n_head=H, vocab_size=V, top_k=TOP_K,
                norm_topk_prob=cfg.moe.norm_topk_prob, eps=cfg.rms_norm_eps,
                theta=cfg.rope_theta)


def bias_router(params, scan):
    """The worst imbalance top-8 of 16 allows: every token sends its eight
    choices to experts 0..7 (expert 3 first), experts 8..15 get no row.
    The embedding rows get a common offset along the all-ones direction, so
    every normalised hidden state has a mean of about 1 and ``h @ (b/E *
    ones)`` acts as a bias ``b`` on the router's logits."""
    out = jax.tree_util.tree_map(lambda x: x, params)
    out["embed_tokens"] = out["embed_tokens"] + 3.0
    bias = jnp.zeros(N_EXP).at[:8].set(6.0).at[3].set(8.0)
    for n in (["layers"] if scan else [f"layers_{i}" for i in range(L)]):
        gate = out[n]["moe"]["gate"]
        gate["wg"] = gate["wg"] * 0.1 + bias / E * jnp.ones_like(gate["wg"])
    return out


CASES = [
    # (id, norm_topk_prob, biased router, scan, loss_chunk)
    ("plain", False, False, False, 0),
    ("norm_topk", True, False, False, 0),
    ("one_expert_takes_all", False, True, False, 0),
    ("scanned", False, False, True, 0),
    ("chunked_head", False, False, False, 32),
    ("scanned_chunked_norm", True, False, True, 40),
]


@pytest.mark.parametrize("norm,biased,scan,chunk",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_loss_and_gradients_match_reference(norm, biased, scan, chunk):
    model, cfg, params, ids = build(norm, scan, chunk)
    if biased:
        params = bias_router(params, scan)
    kw = ref_kw(cfg)

    out, grads = compare.forward_and_gradients(
        lambda p: model.apply({"params": p}, ids, labels=ids), params)
    loss = out["loss"]
    want, want_grads = jax.value_and_grad(
        lambda p: ref.training_loss(p, ids, **kw))(params)
    ce, aux = ref.loss_parts(params, ids, **kw)
    assert abs(float(loss) - float(want)) < TOL
    assert abs(float(out["aux_loss"]) - float(aux)) < TOL
    assert float(aux) > 0.01 * 0.99          # balance loss >= 1, z-loss > 0
    # dropless: every (token, choice) pair reached an expert, in every layer
    counts = np.asarray(out["stats"]["tokens_per_expert"])
    assert counts.shape == (L, N_EXP)
    assert (counts.sum(-1) == 2 * S * TOP_K).all()
    assert int(np.asarray(out["stats"]["dropped"]).sum()) == 0
    if biased:
        assert (counts[:, :8] == 2 * S).all()           # take every token
        assert (counts[:, 8:] == 0).all()               # empty groups
    if not chunk:
        lg = ref.logits(params, ids, **kw)
        np.testing.assert_allclose(np.asarray(out["logits"])[..., :V],
                                   np.asarray(lg)[..., :V], atol=TOL, rtol=0)
    else:
        assert "logits" not in out

    def leaf(tree, layer, *path):
        t = tree["layers"] if scan else tree[f"layers_{layer}"]
        for p in path:
            t = t[p]
        return np.asarray(t[layer] if scan else t)

    chosen = int(np.argmax(counts[1]))       # an expert that saw tokens
    for path in (("moe", "gate", "wg"), ("self_attn", "q_norm", "scale"),
                 ("self_attn", "k_norm", "scale")):
        np.testing.assert_allclose(leaf(grads, 1, *path),
                                   leaf(want_grads, 1, *path), atol=TOL, rtol=0)
    for name in ("gate", "up", "down"):
        got = leaf(grads, 1, "moe", "experts", name)
        exp = leaf(want_grads, 1, "moe", "experts", name)
        np.testing.assert_allclose(got[chosen], exp[chosen], atol=TOL, rtol=0)
        assert np.abs(exp[chosen]).max() > 0
        if biased:                            # an empty group has zero grad
            assert np.abs(got[8:]).max() == 0
    np.testing.assert_allclose(np.asarray(grads["lm_head"]),
                               np.asarray(want_grads["lm_head"]),
                               atol=TOL, rtol=0)


@pytest.mark.parametrize("axes", [{"dp": 1, "fsdp": -1}, {"dp": 2, "fsdp": 4}],
                         ids=["fsdp8", "dp2_fsdp4"])
def test_data_parallel_ranks_sort_their_own_tokens(axes):
    """Under a data-parallel mesh the sorted dispatch is a ``shard_map`` over
    the batch axes: each rank argsorts, groups and multiplies its own
    tokens, the expert leaves replicated.  Loss and gradients (the
    replicated leaves' are summed over the ranks) still equal the
    reference's over the whole batch."""
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh

    model, cfg, params, _ = build()
    kw = ref_kw(cfg)
    ids = jnp.asarray(np.random.default_rng(5).integers(0, V, (8, S)), jnp.int32)
    mesh_mod.set_mesh(build_mesh(MeshConfig(**axes)))

    def sys_loss(p):
        return model.apply({"params": p}, ids, labels=ids)["loss"]

    assert "shard_map" in str(jax.make_jaxpr(sys_loss)(params))
    loss, grads = jax.jit(jax.value_and_grad(sys_loss))(params)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.training_loss(p, ids, **kw))(params)
    assert abs(float(loss) - float(want)) < TOL
    for layer in range(L):
        for path in (("moe", "gate", "wg"), ("moe", "experts", "gate"),
                     ("moe", "experts", "up"), ("moe", "experts", "down"),
                     ("self_attn", "q_proj_kernel")):
            got, exp = grads[f"layers_{layer}"], want_grads[f"layers_{layer}"]
            for k in path:
                got, exp = got[k], exp[k]
            np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                       atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(grads["embed_tokens"]),
                               np.asarray(want_grads["embed_tokens"]),
                               atol=TOL, rtol=0)


def test_bf16_router_or_dropped_pair_would_fail_the_tolerance():
    """The tolerance is tight enough: the reference's own logits with the
    router's matrix rounded to bf16, and with one expert's output dropped,
    each differ from the true ones by more than TOL."""
    model, cfg, params, ids = build()
    kw = ref_kw(cfg)
    want = np.asarray(ref.logits(params, ids, **kw))[..., :V]

    def changed(fn):
        p = jax.tree_util.tree_map(lambda x: x, params)
        fn(p["layers_1"]["moe"])
        return np.abs(np.asarray(ref.logits(p, ids, **kw))[..., :V] - want).max()

    def bf16_router(moe):
        moe["gate"]["wg"] = moe["gate"]["wg"].astype(jnp.bfloat16).astype(jnp.float32)

    def drop_expert(moe):       # every pair routed to expert 0 is lost
        moe["experts"]["down"] = moe["experts"]["down"].at[0].set(0.0)

    assert changed(bf16_router) > 10 * TOL
    assert changed(drop_expert) > 10 * TOL


@pytest.mark.parametrize("top_k", [1, 2])
@pytest.mark.parametrize("act", ["gelu", "swiglu"])
def test_sorted_dispatch_equals_capacity_path(top_k, act):
    """Same parameters, same output: the capacity einsum path with room
    for every pair (capacity >= S*k) and the sorted dropless dispatch.
    GShard's gates renormalise at top-2 and not at top-1."""
    Sx, M, Hd, Ex = 40, 32, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (Sx, M), jnp.float32)
    common = dict(num_experts=Ex, top_k=top_k, expert_act=act)
    cap = MoELayer(MoEConfig(capacity_factor=float(Ex * top_k),
                             eval_capacity_factor=float(Ex * top_k), **common),
                   model_dim=M, hidden_dim=Hd, dtype=jnp.float32)
    srt = MoELayer(MoEConfig(drop_tokens=False, norm_topk_prob=top_k == 2,
                             **common),
                   model_dim=M, hidden_dim=Hd, dtype=jnp.float32)
    params = jax.jit(cap.init)(jax.random.PRNGKey(1), x)
    params = jax.tree_util.tree_map(lambda p: p * 10.0, params)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        jax.eval_shape(srt.init, jax.random.PRNGKey(1), x))
    out_c, aux_c, st_c = cap.apply(params, x, return_stats=True)
    out_s, aux_s, st_s = srt.apply(params, x, return_stats=True)
    assert int(st_c["dropped"]) == 0 and int(st_s["dropped"]) == 0
    np.testing.assert_array_equal(np.asarray(st_c["tokens_per_expert"]),
                                  np.asarray(st_s["tokens_per_expert"]))
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_c),
                               atol=1e-5, rtol=1e-5)
    if top_k == 1:      # GShard's aux counts first choices: same at k = 1
        np.testing.assert_allclose(float(aux_s), float(aux_c), rtol=1e-5)
    g_c = jax.jit(jax.grad(lambda p: cap.apply(p, x)[0].sum()))(params)
    g_s = jax.jit(jax.grad(lambda p: srt.apply(p, x)[0].sum()))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_s),
                    jax.tree_util.tree_leaves(g_c)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_dropped_counts_pairs_outside_every_group():
    """``dropped`` is what the step hands ``moe_dropped_tokens_total``: the
    (token, choice) pairs no expert's group holds.  GShard's queues turn
    pairs away once they are full; the sorted dispatch's groups hold all."""
    Sx, M, Hd, Ex = 40, 32, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (Sx, M), jnp.float32)
    cap = MoELayer(MoEConfig(num_experts=Ex, top_k=2, capacity_factor=0.5,
                             eval_capacity_factor=0.5, min_capacity=1),
                   model_dim=M, hidden_dim=Hd, dtype=jnp.float32)
    params = jax.jit(cap.init)(jax.random.PRNGKey(1), x)
    _, _, st = cap.apply(params, x, return_stats=True)
    kept = int(st["tokens_per_expert"].sum())
    assert kept <= Ex * 5 < Sx * 2 and int(st["dropped"]) == Sx * 2 - kept
    srt = MoELayer(MoEConfig(num_experts=Ex, top_k=2, drop_tokens=False),
                   model_dim=M, hidden_dim=Hd, dtype=jnp.float32)
    _, _, st = srt.apply(params, x, return_stats=True)
    assert int(st["tokens_per_expert"].sum()) == Sx * 2
    assert int(st["dropped"]) == 0


def test_topk_routing_losses():
    logits = jnp.zeros((12, 6), jnp.float32)
    w, idx, counts, balance, z = topk_routing(logits, 3)
    assert w.shape == idx.shape == (12, 3) and int(counts.sum()) == 36
    np.testing.assert_allclose(np.asarray(w), 1 / 6, rtol=1e-6)
    np.testing.assert_allclose(float(z), np.log(6.0) ** 2, rtol=1e-6)
    # uniform probabilities: sum_e f_e * (1/E) * E = 1 whatever the choice
    np.testing.assert_allclose(float(balance), 1.0, rtol=1e-6)
    w = topk_routing(logits, 3, norm_topk_prob=True)[0]
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)


def test_capacity_gate_refuses_top8_and_dropless_refuses_ep():
    x = jnp.ones((8, 16), jnp.float32)
    layer = MoELayer(MoEConfig(num_experts=8, top_k=8), model_dim=16,
                     hidden_dim=8, dtype=jnp.float32)
    with pytest.raises(ValueError, match="drop_tokens=False"):
        layer.init(jax.random.PRNGKey(0), x)
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh

    mesh_mod.set_mesh(build_mesh(MeshConfig(ep=2, dp=-1)))
    layer = MoELayer(MoEConfig(num_experts=8, top_k=8, drop_tokens=False),
                     model_dim=16, hidden_dim=8, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="section 7, row 8"):
        layer.init(jax.random.PRNGKey(0), x)


def test_engine_trains_olmoe_and_feeds_the_routing_counters():
    """deepspeed_tpu.initialize -> train_batch(data_iter=...) with ZeRO-3
    and adamw8bit, as a LlamaConfig; the routing statistics come back with
    the loss and land in the registry without a fence of their own."""
    import deepspeed_tpu
    from deepspeed_tpu.telemetry import get_registry

    # emptied for this test, and as it was after it: what another test's
    # module registered once (the goodput gauges) must outlive this one
    reg = get_registry()
    with reg._lock:
        kept = dict(reg._metrics)
        reg._metrics.clear()
    try:
        _trains_and_feeds_the_counters(deepspeed_tpu, get_registry)
    finally:
        with reg._lock:
            reg._metrics.clear()
            reg._metrics.update(kept)


def _trains_and_feeds_the_counters(deepspeed_tpu, get_registry):
    _, cfg, _, _ = build(loss_chunk=32)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg), config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw8bit", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 3}, "gradient_clipping": 1.0,
            "mesh": {"fsdp": -1}, "steps_per_print": 10**9})
    engine.init_params()
    rows = engine.train_batch_size
    rng = np.random.default_rng(0)

    def batches():
        ids = rng.integers(0, V, (rows, S)).astype(np.int32)
        while True:
            yield {"input_ids": ids, "labels": ids}

    it = batches()
    losses = [float(engine.train_batch(data_iter=it)) for _ in range(6)]
    engine.drain_step_stats(wait=True)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    snap = get_registry().snapshot()
    per_expert = snap["moe_tokens_per_expert"]["samples"]
    assert len(per_expert) == L * N_EXP
    assert sum(s["value"] for s in per_expert) == 6 * L * rows * S * TOP_K
    assert snap["moe_dropped_tokens_total"]["samples"][0]["value"] == 0
    assert snap["moe_aux_loss"]["samples"][0]["value"] >= 0.99
    assert snap["moe_router_z"]["samples"][0]["value"] > 0


def test_drain_step_stats_tolerates_a_model_that_books_none():
    """Any model may return ``out["stats"]``; only one with a
    ``record_step_stats`` gets them handed back."""
    import collections

    from deepspeed_tpu.runtime.engine import Engine

    engine = object.__new__(Engine)
    engine.model = object()
    engine._pending_stats = collections.deque([{"n": jnp.ones(())}])
    engine.drain_step_stats()
    assert not engine._pending_stats


def test_flops_per_token_counts_active_experts():
    _, cfg, _, _ = build()
    dense = LlamaForCausalLM(LlamaConfig(
        vocab_size=V, hidden_size=E, num_hidden_layers=L,
        num_attention_heads=H, intermediate_size=I,
        max_position_embeddings=S)).flops_per_token()
    sparse = LlamaForCausalLM(cfg).flops_per_token()
    extra = 6.0 * L * ((TOP_K - 1) * 3 * E * I + E * N_EXP)
    assert sparse == pytest.approx(dense + extra)


def test_grouped_matmul_tiles_and_cpu_dispatch():
    from deepspeed_tpu.ops.grouped_matmul import (TILES, _tiles,
                                                  grouped_matmul)
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    assert _tiles(65536, 2048, 1024) == _tiles(65536, 1024, 2048) == TILES
    # the largest multiple of 128 up to the cap that divides (PR 30: 384
    # of 384, where halving from 1024 found only 128)
    assert _tiles(1280, 512, 384) == (256, 512, 384)
    assert _tiles(96, 64, 32) is None           # the tiny CPU shapes
    x = jnp.ones((12, 8), jnp.float32)
    w = jnp.stack([jnp.full((8, 4), float(g)) for g in range(3)])
    out = grouped_matmul(x, w, jnp.asarray([5, 0, 7]))
    np.testing.assert_allclose(np.asarray(out[:5]), 0.0)
    np.testing.assert_allclose(np.asarray(out[5:]), 16.0)
    assert any(site == "grouped_matmul" and impl == "ragged_dot"
               and reason == "no tile divides (12, 8) x (3, 8, 4)" and n > 0
               for site, impl, reason, n in dispatch_report())
