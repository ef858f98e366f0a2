"""``drivers/train_lm.py`` for LFM2-MoE: the same engine, data, window,
fences and ``observed`` keys (its ``run``, unchanged), with the set-up's
comparison against the plain reference widened to what this model's loss
cannot see, one comparison after the window, and the device's time by the
program's ``short_conv/`` scopes for the two readers this cell brings.

Before the window, on one seeded row a rank:

1. ``eval_batch`` against the reference's loss (cross-entropy alone: there
   is no router loss);
2. the conv mixer alone, the dense block's and the first sparse block's
   (``models/llama.py ShortConv`` in bf16 on the block's own leaves against
   ``reference.short_conv`` on the reference forward's normalised hidden
   states): its output, over TWO rows (the seeded row and the same row read
   backwards, so that a row's tail leaking into the next row shows) and
   over the first positions of each row alone (where a filter that starts
   from anything but zeros, or reads ahead, is all of the output); and the
   gradients of a seeded scalar of that output with respect to the input
   and the three leaves (the backward is new code too);
3. the attention layer alone against ``reference.attention``;
4. every expert layer alone (router, selection bias, the held experts)
   against ``reference.expert_ffn`` with the same share and **a bias that
   is not zero** (``train_trinity.seeded_bias``);
5. the leading dense layer's FFN alone (``train_trinity.check_dense``).

After it (``train_trinity``'s, this model's routing is its code):

6. each layer's bias equals what ``reference.bias_update`` makes of zero
   over every step this process trained, exactly;
7. the sorted dispatch moved its rows with the Pallas row kernels and the
   short convolution resolved to what the file expects.
"""
from __future__ import annotations

import sys

import numpy as np

from benchmark.drivers import train_lm, train_mellum2, train_trinity

FAMILIES = train_lm.FAMILIES
_rel_err = train_mellum2._rel_err
model_config = train_lm.model_config
CONV, FULL = "conv", "full_attention"
HEAD_POSITIONS = 8      # of each row, read alone by the conv check
SCOPES = ("short_conv/in_proj", "short_conv/filter", "short_conv/out_proj")


def reference_kwargs(conf: dict) -> dict:
    kw = train_trinity.reference_kwargs(conf)
    kw["rope_theta"] = float(conf["rope_parameters"]["rope_theta"])
    return kw


def two_rows(h):
    """``h`` (1, S, E) and the same row read backwards: two rows of
    different content at the timed length."""
    return np.concatenate([np.asarray(h), np.asarray(h)[:, ::-1]], axis=0)


def read_conv(cfg, reference, p_conv, h, **wrong) -> tuple:
    """``(error over everything, error over the first HEAD_POSITIONS of
    each row)`` of the program's conv mixer against the reference's, as
    shares of the reference's norm there."""
    import jax

    from deepspeed_tpu.models.llama import ShortConv

    module = ShortConv(cfg)
    got = np.asarray(jax.jit(lambda p, h: module.apply({"params": p}, h))(
        p_conv, h), np.float32)
    want = np.asarray(reference.short_conv(p_conv, h, **wrong))
    n = HEAD_POSITIONS
    return _rel_err(got, want), _rel_err(got[:, :n], want[:, :n])


def read_conv_grads(ctx, cfg, reference, p_conv, h, layer: int,
                    **wrong) -> dict:
    """Relative error of d(sum(y * probe)) / d(h, each leaf), program
    against reference, under a seeded probe."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import ShortConv

    module = ShortConv(cfg)
    rng = np.random.default_rng([int(ctx.seed) & 0xFFFFFFFF, 45, layer])
    probe = rng.standard_normal(h.shape).astype(np.float32)

    def scalar(h, p):
        y = module.apply({"params": p}, h)
        return (y.astype(jnp.float32) * probe).sum()

    dh, dp = jax.jit(jax.grad(scalar, (0, 1)))(h, p_conv)
    rh, rp = reference.short_conv_grads(p_conv, h, probe, **wrong)
    errs = {"dh": _rel_err(dh, rh)}
    errs.update({"d" + leaf: _rel_err(dp[leaf], rp[leaf]) for leaf in rp})
    return errs


def conv_layers_checked(cfg) -> list:
    """The first conv layer (the dense block's) and the first sparse one."""
    convs = [i for i, k in enumerate(cfg.kinds) if k == CONV]
    return sorted({convs[0], next(i for i in convs if cfg.sparse(i))})


def check_conv(ctx, cfg, conf, reference, params, hidden) -> None:
    """Comparison 2, on the dense block's mixer and the first sparse
    block's."""
    tol = conf["reference_check"]
    leaves = list(reference.layers(params, len(hidden)))
    for i in conv_layers_checked(cfg):
        p = leaves[i]["conv"]
        h = two_rows(hidden[i]).astype(cfg.dtype)
        whole, heads = read_conv(cfg, reference, p, h)
        ctx.log(f"conv check: layer {i} |program - reference| / |reference| "
                f"{whole:.5f}, over the first {HEAD_POSITIONS} positions of "
                f"each row {heads:.5f}")
        err = max(whole, heads)
        ctx.check(np.isfinite(err) and err <= tol["conv_rel_tol"],
                  f"layer {i}: the conv mixer's output differs from the "
                  f"reference's by {err:.5f} of its norm, more than "
                  f"{tol['conv_rel_tol']}")
        grads = read_conv_grads(ctx, cfg, reference, p, h, i)
        ctx.log(f"conv gradient check: layer {i} " + " ".join(
            f"{k} {v:.5f}" for k, v in grads.items()))
        err = max(grads.values())
        ctx.check(np.isfinite(err) and err <= tol["conv_grad_rel_tol"],
                  f"layer {i}: a gradient of the conv mixer differs from "
                  f"the reference's by {err:.5f} of its norm, more than "
                  f"{tol['conv_grad_rel_tol']}: {grads}")


def read_attention(cfg, reference, p_attn, h, kw, **wrong) -> float:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaAttention

    module = LlamaAttention(cfg, FULL)
    pos = jnp.arange(h.shape[1])[None, :]
    got = jax.jit(lambda p, h: module.apply({"params": p}, h, pos, None))(
        p_attn, h)
    want = reference.attention(
        FULL, p_attn, h, n_head=cfg.num_attention_heads,
        n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
        rope_theta=kw["rope_theta"], eps=kw["eps"], **wrong)
    return _rel_err(got, want)


def check_attention(ctx, cfg, conf, reference, params, hidden) -> None:
    """Comparison 3: the first attention layer alone (bf16 compute, the
    per-head norm, rotary, grouped queries through the flash kernel)."""
    tol = conf["reference_check"]["attention_rel_tol"]
    i = list(cfg.kinds).index(FULL)
    p = list(reference.layers(params, len(hidden)))[i]["self_attn"]
    err = read_attention(cfg, reference, p, hidden[i].astype(cfg.dtype),
                         reference_kwargs(conf))
    ctx.log(f"attention check: layer {i} ({FULL}) |program - reference| / "
            f"|reference| {err:.5f}")
    ctx.check(np.isfinite(err) and err <= tol,
              f"layer {i}: the attention layer's output differs from the "
              f"reference's by {err:.5f} of its norm, more than {tol}")


def read_experts(ctx, cfg, conf, reference, params, hidden, **wrong) -> list:
    """Each expert layer's error under a seeded bias."""
    import jax

    from deepspeed_tpu.parallel.moe import MoELayer

    layer = MoELayer(cfg.moe, model_dim=cfg.hidden_size,
                     hidden_dim=cfg.expert_size, dtype=cfg.dtype)
    run = jax.jit(lambda p, h: layer.apply({"params": p}, h)[0])
    leaves = list(reference.layers(params, len(hidden)))
    errs = []
    for i in range(cfg.num_dense_layers, len(hidden)):
        h = hidden[i].astype(cfg.dtype)
        p = dict(leaves[i]["moe"])
        p["gate"] = dict(p["gate"], expert_bias=train_trinity.seeded_bias(
            ctx.seed, i, p, h))
        want = reference.expert_ffn(
            p, h, top_k=conf["num_experts_per_tok"],
            route_scale=conf["routed_scaling_factor"],
            first_expert=cfg.moe.first_expert, **wrong)
        errs.append(_rel_err(run(p, h), want))
    return errs


def check_experts(ctx, cfg, conf, reference, params, hidden) -> None:
    tol = conf["reference_check"]["expert_rel_tol"]
    errs = read_experts(ctx, cfg, conf, reference, params, hidden)
    ctx.log("expert check: |MoE layer - reference FFN| / |reference FFN| a "
            "layer, under a seeded bias " + " ".join(f"{e:.5f}" for e in errs))
    ctx.check(max(errs) <= tol and all(np.isfinite(errs)),
              f"an expert layer's output differs from the reference's sparse "
              f"FFN by {max(errs):.5f} of its norm, more than {tol}")


def check_reference(ctx, engine, cfg, conf, reference, batches) -> float:
    """Comparisons 1 to 5; returns the engine's loss."""
    tol = conf["reference_check"]
    rows = engine.dp_world
    ids = next(batches)["input_ids"][:rows]
    got = float(engine.eval_batch({"input_ids": ids, "labels": ids}))
    ffn_in, mixer_in = [], []
    want = float(reference.loss_parts(
        engine.state.params, ids, **reference_kwargs(conf),
        ffn_inputs=ffn_in, mixer_inputs=mixer_in)[0])
    ctx.log(f"reference check: engine loss {got:.6f}  reference {want:.6f} "
            f"(cross-entropy alone)  difference {got - want:+.6f}")
    ctx.check(abs(got - want) <= tol["loss_abs_tol"],
              f"eval loss {got} differs from the reference {want} by more "
              f"than {tol['loss_abs_tol']}")
    params = engine.state.params
    check_conv(ctx, cfg, conf, reference, params, mixer_in)
    check_attention(ctx, cfg, conf, reference, params, mixer_in)
    check_experts(ctx, cfg, conf, reference, params, ffn_in)
    train_trinity.check_dense(ctx, cfg, conf, reference, params, ffn_in)
    return got


def scope_split(ctx, engine, batches) -> dict:
    """Device ms a step under each ``short_conv/`` scope, under the three
    together and of the whole step, from a short profiler session of its
    own after the window (the v5e's device events carry no scope:
    ``engine.profile_device_scopes``)."""
    table = engine.profile_device_scopes(batches, steps=4, depth=4)
    out = {"step": table["device_ms_a_step"]}
    for scope in SCOPES:
        out[scope] = sum(r["ms_a_step"] for r in table["scopes"]
                         if scope in r["scope"])
    out["short_conv"] = sum(out[scope] for scope in SCOPES)
    ctx.log("device ms a step under " + ", ".join(
        f"{scope} {out[scope]:.3f}" for scope in SCOPES)
        + f" of {out['step']:.3f}")
    return out


def run(ctx, reference) -> dict:
    """``train_trinity.run`` (this model's routing is its code: every
    step's counts kept, the bias comparison after the window, the row
    kernels' dispatch) with this module's comparison in place of its own
    and the engine kept for the scopes."""
    import dataclasses
    import importlib

    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    if "conv_L_cache" not in {f.name for f in dataclasses.fields(LlamaConfig)}:
        sys.exit("benchmark: this program's LlamaConfig has no conv layer "
                 "type (conv_L_cache): it cannot run a short-convolution "
                 f"block ({ctx.cell.name})")
    built = []

    def build(ctx):
        built.append(theirs["build"](ctx))
        return built[-1]

    with train_trinity._in_place_of(train_lm, build=build) as theirs, \
            train_trinity._in_place_of(
                train_trinity, check_reference=check_reference):
        out = train_trinity.run(ctx, reference)
    engine, cfg, conf = built[-1]
    rows = {(s, i) for s, i, _, n in dispatch_report() if n}
    want = conf.get("expect_short_conv_impl")
    ctx.check(ctx.rehearse or want is None or ("short_conv", want) in rows,
              f"short_conv never resolved to {want}: {sorted(rows)}")
    obs = out["observed"]
    flops = importlib.import_module("benchmark." + conf["flops"])
    step_tokens = obs["tokens"] // max(obs["steps"], 1) // obs["n_devices"]
    obs["short_conv_filter_flops_per_step"] = \
        flops.short_conv_filter_flops_per_step(conf, step_tokens)
    obs["short_conv_filter_bytes_per_step"] = \
        flops.short_conv_filter_bytes_per_step(conf, step_tokens)
    if ctx.trace and not ctx.rehearse:
        from benchmark import loadgen

        batches = loadgen.packed_batches(
            ctx.sized(ctx.cell.traffic), ctx.seed + 1,
            engine.train_batch_size, cfg.vocab_size)
        obs["device_scope_ms"] = scope_split(ctx, engine, batches)
    return out
