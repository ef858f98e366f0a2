"""``drivers/train_lm.py`` for JoyAI-LLM-Flash (the DeepSeek-V3 family):
the same engine, data, window, fences and ``observed`` keys (its ``run``,
unchanged), with the set-up's comparison against the plain reference
widened to what this model's loss cannot see, and one comparison after the
window.  ``drivers/train_trinity.py``'s form; what is new is the latent
attention and the prediction block.

Before the window, on one seeded row a rank (``reference_check`` of the
configuration file has each limit and its readings):

a. ``eval_batch`` against the reference's CE_main + 0.3 CE_mtp, and the
   model's two losses each alone against the reference's;
b. one sparse block's attention and the prediction block's attention alone
   (``LlamaLatentAttention``: bf16 projections, the latent norms, rotary on
   the rope channels, the two-product flash kernels) against
   ``reference.attention`` on the same normalised hidden states;
c. every expert layer alone, the prediction block's too, under a seeded
   bias that is not zero, shared expert included;
d. the prediction block alone from the reference's ``h``: the per-position
   negative log-likelihood of the token two ahead through the model's own
   table and head;
e. the leading dense FFN alone.

After it:

f. each layer's bias (the prediction block's too) equals what
   ``reference.bias_update`` makes of zero over every step's counts;
g. beside ``train_lm.run``'s own window checks: the flash kernels at site
   ``attention`` for all six blocks and the XLA path for none, the share's
   rows moved by the Pallas row kernels.
"""
from __future__ import annotations

import numpy as np

from benchmark.drivers import train_lm, train_mellum2, train_trinity

FAMILIES = train_lm.FAMILIES
_rel_err = train_mellum2._rel_err
model_config = train_lm.model_config
seeded_bias = train_trinity.seeded_bias
check_dense = train_trinity.check_dense
log_balance = train_trinity.log_balance
_in_place_of = train_trinity._in_place_of
MLA_REASON = "shared rope lanes"


def reference_kwargs(conf: dict) -> dict:
    kw = {arg: conf[key] for arg, key in conf["reference_args"].items()}
    kw["first_expert"] = int(conf["moe"].get("first_expert", 0))
    return kw


def _attn_kwargs(kw: dict) -> dict:
    return {k: kw[k] for k in (
        "n_head", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rope_theta", "eps")}


def reference_forward(reference, params, ids, conf, **extra) -> dict:
    """Everything the comparisons need of the reference, one forward: the
    stack's output, both per-position losses, and the normalised inputs of
    every attention and FFN, the prediction block's last."""
    kw = dict(reference_kwargs(conf), **extra)
    attn_in, ffn_in = [], []
    h = reference.hidden(params, ids, attn_inputs=attn_in, ffn_inputs=ffn_in,
                         **kw)
    main = reference.main_nll(h, ids, params, **kw)
    second = reference.mtp(h, ids, params, attn_inputs=attn_in,
                           ffn_inputs=ffn_in, **kw)
    return {"h": h, "main_nll": main, "mtp_nll": second, "attn_in": attn_in,
            "ffn_in": ffn_in}


def blocks(reference, params, cfg) -> list:
    """Each block's leaves, the prediction block's last."""
    return list(reference.layers(params, cfg.num_hidden_layers)) \
        + [params["mtp_0"]["block"]]


def read_losses(engine, ids) -> tuple:
    """``(eval_batch's loss, the model's main loss, its second)`` on
    ``ids``: the first is the engine's own evaluation step, the other two
    the same model and weights with both parts kept."""
    import jax

    got = float(engine.eval_batch({"input_ids": ids, "labels": ids}))
    out = jax.jit(lambda p, x: (lambda o: (o["lm_loss"], o["mtp_loss"]))(
        engine.model.apply({"params": p}, x, labels=x)))(
        engine.state.params, engine._shard_batch({"input_ids": ids})[
            "input_ids"])
    return got, float(out[0]), float(out[1])


def read_attention(cfg, reference, p_attn, h, kw, **ref_extra) -> float:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaLatentAttention

    module = LlamaLatentAttention(cfg)
    h = h.astype(cfg.dtype)
    pos = jnp.arange(h.shape[1])[None, :]
    got = jax.jit(lambda p, h: module.apply({"params": p}, h, pos, None))(
        p_attn, h)
    return _rel_err(got, reference.attention(h, p_attn, **_attn_kwargs(kw),
                                             **ref_extra))


def read_experts(ctx_seed, cfg, conf, reference, leaves, ffn_in,
                 **ref_extra) -> list:
    """Comparison c: one error a sparse block, the prediction block's
    last."""
    import jax

    from deepspeed_tpu.parallel.moe import MoELayer

    layer = MoELayer(cfg.moe, model_dim=cfg.hidden_size,
                     hidden_dim=cfg.expert_size, dtype=cfg.dtype)
    run = jax.jit(lambda p, h: layer.apply({"params": p}, h)[0])
    errs = []
    for i in range(cfg.num_dense_layers, len(leaves)):
        h = ffn_in[i].astype(cfg.dtype)
        p = dict(leaves[i]["moe"])
        p["gate"] = dict(p["gate"],
                         expert_bias=seeded_bias(ctx_seed, i, p, h))
        want = reference.sparse_ffn(
            p, h, top_k=conf["num_experts_per_tok"],
            route_scale=conf["routed_scaling_factor"],
            first_expert=cfg.moe.first_expert, **ref_extra)
        errs.append(_rel_err(run(p, h), want))
    return errs


def program_mtp_nll(cfg, params, h, ids):
    """The program's prediction block alone from ``h`` (B, S, E): the
    per-position negative log-likelihood (B, S - 2) of the token two
    ahead, through ``params``' own table and head (bf16 compute)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import MTPModule

    module = MTPModule(cfg)

    def run(params, h, ids):
        nxt = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], 1)
        emb = params["embed_tokens"].astype(cfg.dtype)[nxt]
        pos = jnp.arange(ids.shape[1])[None, :]
        x, _ = module.apply({"params": params["mtp_0"]}, h.astype(cfg.dtype),
                            emb, (pos, None))
        logits = jnp.dot(x[:, :-2], params["lm_head"].astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
        logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab_size,
                           logits, -jnp.inf)
        return jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, ids[:, 2:, None], -1)[..., 0]

    return jax.jit(run)(params, h, jnp.asarray(ids))


def check_reference(ctx, engine, cfg, conf, reference, batches) -> float:
    """Comparisons a to e; returns the engine's loss."""
    tol = conf["reference_check"]
    ids = next(batches)["input_ids"][:engine.dp_world]
    params = engine.state.params
    kw = reference_kwargs(conf)
    lam = float(conf["model_options"].get("mtp_loss_weight", 0.3))
    got, got_main, got_mtp = read_losses(engine, ids)
    ref = reference_forward(reference, params, ids, conf)
    main, second = float(ref["main_nll"].mean()), float(ref["mtp_nll"].mean())
    want = main + lam * second
    ctx.log(f"reference check: engine loss {got:.6f}  reference {want:.6f} "
            f"(main {main:.6f} + {lam} x second {second:.6f})  difference "
            f"{got - want:+.6f}; main alone {got_main - main:+.6f}, second "
            f"alone {got_mtp - second:+.6f}")
    for what, a, b in (("eval loss", got, want), ("main loss", got_main, main),
                       ("second (MTP) loss", got_mtp, second)):
        ctx.check(abs(a - b) <= tol["loss_abs_tol"],
                  f"{what} {a} differs from the reference {b} by more than "
                  f"{tol['loss_abs_tol']}")
    leaves = blocks(reference, params, cfg)
    # b: the first sparse block and the prediction block
    for i in (cfg.num_dense_layers, len(leaves) - 1):
        err = read_attention(cfg, reference, leaves[i]["self_attn"],
                             ref["attn_in"][i], kw)
        name = "prediction block" if i == len(leaves) - 1 else f"layer {i}"
        ctx.log(f"attention check: {name} |program - reference| / "
                f"|reference| {err:.5f}")
        ctx.check(np.isfinite(err) and err <= tol["attention_rel_tol"],
                  f"{name}: the latent attention's output differs from the "
                  f"reference's by {err:.5f} of its norm, more than "
                  f"{tol['attention_rel_tol']}")
    # c
    errs = read_experts(ctx.seed, cfg, conf, reference, leaves, ref["ffn_in"])
    ctx.log("expert check: |MoE layer - reference FFN| / |reference FFN| a "
            "layer (the prediction block's last), under a seeded bias "
            + " ".join(f"{e:.5f}" for e in errs))
    ctx.check(max(errs) <= tol["expert_rel_tol"] and all(np.isfinite(errs)),
              f"an expert layer's output differs from the reference's sparse "
              f"FFN by {max(errs):.5f} of its norm, more than "
              f"{tol['expert_rel_tol']}")
    # d
    err = _rel_err(program_mtp_nll(cfg, params, ref["h"], ids),
                   ref["mtp_nll"])
    ctx.log(f"prediction-block check: |program - reference| / |reference| "
            f"of the per-position loss from the reference's h {err:.5f}")
    ctx.check(np.isfinite(err) and err <= tol["mtp_rel_tol"],
              f"the prediction block's per-position loss from the "
              f"reference's h differs from the reference's by {err:.5f} of "
              f"its norm, more than {tol['mtp_rel_tol']}")
    # e
    check_dense(ctx, cfg, conf, reference, params, ref["ffn_in"])
    return got


def check_bias(ctx, engine, cfg, conf, reference, steps) -> None:
    """Comparison f.  The weights are made with a zero bias; ``steps``
    holds the (biased layers, experts) counts of every step since."""
    engine.drain_step_stats(wait=True)
    rate = float(conf["moe"]["bias_update_rate"])
    ok = ctx.check(0 < len(steps) == engine.global_steps,
                   f"the driver saw the counts of {len(steps)} steps of "
                   f"the {engine.global_steps} the engine trained")
    if not ok:
        return
    leaves = blocks(reference, engine.state.params, cfg)[cfg.num_dense_layers:]
    moved = []
    for row, p in enumerate(leaves):
        got = np.asarray(p["moe"]["gate"]["expert_bias"])
        want = np.zeros_like(got)
        for counts in steps:
            want = reference.bias_update(counts[row], want, rate)
        moved.append(float(np.ptp(got)))
        ctx.check(np.array_equal(got, want),
                  f"biased layer {row}: the selection bias after "
                  f"{len(steps)} steps differs from reference.bias_update "
                  f"over their counts at {int((got != want).sum())} of "
                  f"{got.size} experts (largest difference "
                  f"{float(np.abs(got - want).max()):.6f})")
    ctx.log(f"bias check: {len(steps)} steps x {len(moved)} layers (the "
            f"prediction block's last) against reference.bias_update, "
            f"exactly; max - min a layer "
            + " ".join(f"{m:.4f}" for m in moved))


def run(ctx, reference) -> dict:
    """``train_lm.run`` with this module's comparison in place of its own,
    the engine kept for the comparison after the window, and every step's
    counts kept as the program books them."""
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report
    from deepspeed_tpu.parallel import moe

    built, steps = [], []

    def build(ctx):
        built.append(theirs["build"](ctx))
        return built[-1]

    def record_stats(stats):
        counts = np.asarray(stats["tokens_per_expert"])
        steps.append(counts.reshape(-1, counts.shape[-1]))
        booked["record_stats"](stats)

    with _in_place_of(train_lm, check_reference=check_reference,
                      build=build) as theirs, \
            _in_place_of(moe, record_stats=record_stats) as booked:
        out = train_lm.run(ctx, reference)
        engine, cfg, conf = built[-1]
        check_bias(ctx, engine, cfg, conf, reference, steps)
    if 0 < out["attempted"] <= len(steps):
        log_balance(ctx, cfg, steps, out["attempted"])
    report = [r for r in dispatch_report() if r[3]]
    if not ctx.rehearse:
        flash = sum(n for s, i, r, n in report
                    if (s, i) == ("attention", "flash") and MLA_REASON in r)
        n_blocks = cfg.num_hidden_layers + cfg.num_nextn_predict_layers
        ctx.check(flash >= n_blocks,
                  f"the two-product flash kernels were dispatched {flash} "
                  f"times for {n_blocks} blocks: {report}")
        xla = [r for r in report if r[:2] == ("attention", "jnp")]
        ctx.check(not xla, f"attention took the XLA path: {xla}")
        ctx.check(any(r[:2] == ("moe_rows", "pallas") for r in report),
                  f"the share's rows never moved through the Pallas row "
                  f"kernels: {report}")
    train_mellum2.count_what_was_routed_here(ctx, out)
    return out
