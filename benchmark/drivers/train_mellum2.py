"""``drivers/train_lm.py`` for a configuration whose loss cannot see two of
its mechanisms: the same engine, data, window, fences and ``observed`` keys
(its ``run``, unchanged), with the set-up's comparison against the plain
reference widened.

``eval_batch`` against the reference's loss, as there; every MoE layer
alone against the reference's sparse FFN **with the share** (the layer is
built at ``cfg.expert_size`` and told which experts it holds; the
reference gets the same ``first_expert``); and — new — one sliding layer
and the full layer alone against ``reference.attention`` on the same
normalised hidden states.  In the loss a window one key too long, the
wrong rotary table or key-value heads paired ``h % 4`` for ``h // 8`` move
little at initialisation (the attention output is a small part of the
residual stream); alone, attention is all of the output.
"""
from __future__ import annotations

import numpy as np

from benchmark.drivers import train_lm

FAMILIES = train_lm.FAMILIES
model_config = train_lm.model_config


def reference_kwargs(conf: dict) -> dict:
    kw = train_lm.reference_kwargs(conf)
    kw["first_expert"] = int(conf["moe"].get("first_expert", 0))
    return kw


def _rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check_experts(ctx, cfg, conf, reference, params, hidden) -> None:
    """``train_lm.check_experts`` for a share: the layer under test has the
    experts' own width and the configuration's ``MoEConfig`` (router over
    all routed experts, the held run), the reference the same run."""
    import jax

    from deepspeed_tpu.parallel.moe import MoELayer

    layer = MoELayer(cfg.moe, model_dim=cfg.hidden_size,
                     hidden_dim=cfg.expert_size, dtype=cfg.dtype)
    run = jax.jit(lambda p, h: layer.apply({"params": p}, h)[0])
    tol = conf["reference_check"]["expert_rel_tol"]
    errs = []
    for p, h in zip(reference.layers(params, len(hidden)), hidden):
        h = h.astype(cfg.dtype)
        want = reference.expert_ffn(
            p["moe"], h, top_k=conf["num_experts_per_tok"],
            norm_topk_prob=conf["norm_topk_prob"],
            first_expert=cfg.moe.first_expert)
        errs.append(_rel_err(run(p["moe"], h), want))
    ctx.log("expert check: |MoE layer - reference FFN| / |reference FFN| a "
            "layer " + " ".join(f"{e:.5f}" for e in errs))
    ctx.check(max(errs) <= tol and all(np.isfinite(errs)),
              f"a MoE layer's output differs from the reference's sparse FFN "
              f"by {max(errs):.5f} of its norm, more than {tol}")


def check_attention(ctx, cfg, conf, reference, params, hidden) -> None:
    """The first layer of each type alone, the program's attention module
    (bf16 compute, the flash kernel of that type) against
    ``reference.attention`` on ``hidden[i]``, the reference forward's
    normalised hidden states before layer i's attention rounded to the
    compute type."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaAttention

    tol = conf["reference_check"]["attention_rel_tol"]
    kinds = list(cfg.kinds)
    leaves = list(reference.layers(params, len(hidden)))
    for kind in dict.fromkeys(kinds):          # each type once, in order
        i = kinds.index(kind)
        module = LlamaAttention(cfg, kind)
        h = hidden[i].astype(cfg.dtype)
        pos = jnp.arange(h.shape[1])[None, :]
        got = jax.jit(lambda p, h: module.apply({"params": p}, h, pos, None))(
            leaves[i]["self_attn"], h)
        want = reference.attention(
            kind, leaves[i]["self_attn"], h,
            n_head=cfg.num_attention_heads, n_kv_head=cfg.kv_heads,
            head_dim=cfg.head_dim, sliding_window=conf["sliding_window"],
            rope_parameters=conf["rope_parameters"])
        err = _rel_err(got, want)
        ctx.log(f"attention check: layer {i} ({kind}) |program - reference| "
                f"/ |reference| {err:.5f}")
        ctx.check(np.isfinite(err) and err <= tol,
                  f"layer {i} ({kind}): the attention layer's output differs "
                  f"from the reference's by {err:.5f} of its norm, more "
                  f"than {tol}")


def check_reference(ctx, engine, cfg, conf, reference, batches) -> float:
    """``train_lm.check_reference`` plus the two layer checks."""
    tol = conf["reference_check"]
    rows = engine.dp_world
    ids = next(batches)["input_ids"][:rows]
    got = float(engine.eval_batch({"input_ids": ids, "labels": ids}))
    ffn_in, attn_in = [], []
    ce, aux = reference.loss_parts(
        engine.state.params, ids, **reference_kwargs(conf),
        ffn_inputs=ffn_in, attn_inputs=attn_in)
    want = float(ce) + float(aux)
    ctx.log(f"reference check: engine loss {got:.6f}  reference {want:.6f} "
            f"(cross-entropy {float(ce):.6f} + router losses {float(aux):.6f})"
            f"  difference {got - want:+.6f}")
    ctx.check(abs(got - want) <= tol["loss_abs_tol"],
              f"eval loss {got} differs from the reference {want} by more "
              f"than {tol['loss_abs_tol']}")
    check_experts(ctx, cfg, conf, reference, engine.state.params, ffn_in)
    check_attention(ctx, cfg, conf, reference, engine.state.params, attn_in)
    return got


def count_what_was_routed_here(ctx, out: dict) -> None:
    """``train_lm.run`` counts the expert rows from shapes, which for a
    share means the EVEN share of the pairs.  What this chip had to
    multiply is what the routing sent to its experts: where the program's
    counter was read, the required operations and bytes of the grouped
    matmuls and of the whole step are counted from all the pairs the
    window routed here over all it routed
    (``moe_held_pair_pct.of_the_window``: totals, as the device time they
    are divided by sums the steps and layers), so that a routing that sends
    more or fewer rows here cannot read as a faster or slower kernel."""
    import importlib
    import types

    from benchmark.layer_metrics import moe_held_pair_pct

    obs = out["observed"]
    conf = ctx.sized(ctx.cell.config)      # a rehearsal's sizes in one
    held = moe_held_pair_pct.of_the_window(
        dict(obs, cell=types.SimpleNamespace(config=conf)))
    if held is None:
        return
    flops = importlib.import_module("benchmark." + conf["flops"])
    seq = int(ctx.sized(ctx.cell.traffic)["seq_len"])
    step_tokens = obs["tokens"] // obs["steps"] // obs["n_devices"]
    ctx.log(f"{100 * held:.2f}% of the window's pairs were routed to the "
            f"experts held here (even share "
            f"{100 * flops.held_share(conf):.2f}%): required operations "
            f"counted from that")
    obs["flops_per_token"] = flops.train_flops_per_token(conf, seq, held)
    obs["expert_gemm_flops_per_step"] = flops.expert_gemm_flops_per_step(
        conf, step_tokens, held)
    obs["expert_gemm_bytes_per_step"] = flops.expert_gemm_bytes_per_step(
        conf, step_tokens, held=held)


def run(ctx, reference) -> dict:
    """``train_lm.run`` with this module's comparison in place of its own:
    it looks ``check_reference`` up in its module at the call."""
    theirs = train_lm.check_reference
    train_lm.check_reference = check_reference
    try:
        out = train_lm.run(ctx, reference)
    finally:
        train_lm.check_reference = theirs
    count_what_was_routed_here(ctx, out)
    return out
