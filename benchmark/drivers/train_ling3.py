"""``drivers/train_lm.py`` for Ling-3.0-flash: the same engine, data, window,
fences and ``observed`` keys (its ``run``, unchanged), with the set-up's
comparison against the plain reference widened to what this model's loss
cannot see, one comparison after the window, and the device's time by the
program's ``linear_attn/`` scopes for the readers this cell brings.
``drivers/train_qwen3next.py``'s form for the mixer and
``drivers/train_joyai.py``'s for the latent attention and the experts,
``drivers/train_trinity.py``'s for the bias; what is new is the decay a key channel, the gate a head and the group
limit.

Before the window, on one seeded row a rank (``reference_check`` of the
configuration file has each limit and its readings):

1. ``eval_batch`` against the reference's loss (CE_main, + the weighted
   CE_mtp where the configuration keeps the prediction block);
2. **the LAST Kimi-Delta-Attention mixer of the period alone** under seeded
   1-D leaves (``A_log``, ``dt_bias``, the norm's weight moved so that the
   decays spread over (kda_lower_bound, 0) and are not all at one end:
   :func:`moved`), ``models/llama.py KimiDeltaAttention`` in bf16 (the
   chunked rule) against ``reference.kda`` (float32, the recurrence one
   position a step) on the reference forward's normalised hidden states,
   over TWO rows (the seeded row and the same row read backwards) and over
   the first 64 positions of each row alone; and the gradients of a seeded
   scalar of that output with respect to the input and every leaf;
3. the latent-attention layer alone (its gate a head included);
4. every expert layer alone under a seeded bias that is not zero, the
   group limit binding (the share of the pairs it moved is logged and must
   be above zero);
5. the leading dense FFN alone.

After it:

6. each layer's bias equals what ``reference.bias_update`` makes of zero
   over every step's counts;
7. beside ``train_lm.run``'s own window checks: the two-product flash
   kernels at site ``attention`` for the latent-attention layer and the XLA
   path for none, the share's rows moved by the Pallas row kernels, and the
   delta rule run under a decay a key channel, whatever implements it
   (:func:`check_delta_rule`: the site resolved, and the program's gauge
   reads ``expect_gated_delta_decay_channels`` log-decays a head).
"""
from __future__ import annotations

import concurrent.futures
import sys

import numpy as np

from benchmark.drivers import (train_lm, train_mellum2, train_qwen3next,
                               train_trinity)
from benchmark.layer_metrics import _program

FAMILIES = train_lm.FAMILIES
_rel_err = train_mellum2._rel_err
model_config = train_lm.model_config
seeded_bias = train_trinity.seeded_bias
check_dense = train_trinity.check_dense
check_bias = train_trinity.check_bias
log_balance = train_trinity.log_balance
_in_place_of = train_trinity._in_place_of
two_rows = train_qwen3next.two_rows
_mixer_err = train_qwen3next._mixer_err
KDA, FULL = "kda_attention", "full_attention"
MLA_REASON = "shared rope lanes"
DECAY_GAUGE = "gated_delta_decay_channels"
SCOPES = ("linear_attn/in_proj", "linear_attn/conv", "linear_attn/decay_gate",
          "linear_attn/delta_rule", "linear_attn/gated_norm",
          "linear_attn/out_proj")
# the leaves whose gradient passes through g = lower_bound * sigmoid(...)
DECAY_SIDE = {"dA_log", "ddt_bias", "df_proj_kernel"}
# the program's side of a comparison runs on this thread while the
# reference's compiles on the caller's (``train_qwen3next.py``'s way)
_BESIDE = concurrent.futures.ThreadPoolExecutor(1)
# comparison 2 runs on this one beside the evaluation step's compile and
# comparisons 3 to 5: its reference is 8,192 sequential positions forward,
# again and back (35-39 s on the v5e, compiled or not), during which the
# others' executables are built; from an empty compile cache the process
# stood at 348 s of the 360 a run may take with comparison 2 waiting for the
# evaluation step
_ASIDE = concurrent.futures.ThreadPoolExecutor(1)


def reference_kwargs(conf: dict) -> dict:
    kw = {arg: conf[key] for arg, key in conf["reference_args"].items()}
    kw["first_expert"] = int(conf["moe"].get("first_expert", 0))
    kw["layer_types"] = tuple(kw["layer_types"])
    if not conf["model_options"].get("mtp_loss_weight"):
        kw["mtp_layers"] = 0    # at weight 0 the program builds no block
    return kw


def _only(kw: dict, *names) -> dict:
    return {k: kw[k] for k in names}


def kda_kwargs(kw: dict) -> dict:
    return _only(kw, "n_head", "lower_bound", "eps")


def attn_kwargs(kw: dict) -> dict:
    return _only(kw, "n_head", "kv_lora_rank", "qk_nope_head_dim",
                 "qk_rope_head_dim", "v_head_dim", "rope_theta", "eps")


def route_kwargs(kw: dict) -> dict:
    return _only(kw, "top_k", "route_scale", "first_expert", "n_group",
                 "topk_group")


def blocks(reference, params, cfg) -> list:
    """Each block's leaves, the prediction block's (where kept) last."""
    out = list(reference.layers(params, cfg.num_hidden_layers))
    if "mtp_0" in params:
        out.append(params["mtp_0"]["block"])
    return out


def moved(seed: int, layer: int, tree):
    """``tree`` with every 1-D leaf moved from the seed: ``A_log`` drawn
    anew as ``log U(0.5, 2)`` and ``dt_bias`` as ``N(0, 1)``, so that the
    gate's argument spreads over a few units either side of zero and the
    log-decays over (kda_lower_bound, 0) (at the released initial values
    nearly every channel sits at one end, where a wrong decay reads sound);
    the norms' weights by normal noise of 0.2."""
    import jax

    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 58, layer])

    def one(path, x):
        if x.ndim != 1:     # a matrix stays where it is (on the device)
            return x
        x = np.asarray(x)
        name = jax.tree_util.keystr(path)
        if name.endswith("['A_log']"):
            return np.log(rng.uniform(0.5, 2.0, x.shape)).astype(x.dtype)
        if name.endswith("['dt_bias']"):
            return rng.normal(0.0, 1.0, x.shape).astype(x.dtype)
        return (x + rng.normal(0.0, 0.2, x.shape)).astype(x.dtype)

    return jax.tree_util.tree_map_with_path(one, tree)


def read_kda_grads(ctx, cfg, reference, p_kda, h, layer: int, kw,
                   **wrong) -> dict:
    """``{"y": ..., "dh": ..., "d<leaf>": ...}``: relative error of the
    mixer's output and of d(sum(y * probe)) / d(h, each leaf), program
    against reference, under a seeded probe; one executable a side."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import KimiDeltaAttention

    module = KimiDeltaAttention(cfg)
    rng = np.random.default_rng([int(ctx.seed) & 0xFFFFFFFF, 59, layer])
    probe = rng.standard_normal(h.shape).astype(np.float32)

    def both(h, p):
        y, pull = jax.vjp(lambda h, p: module.apply({"params": p}, h), h, p)
        return (y,) + pull(jnp.asarray(probe, y.dtype))

    got = _BESIDE.submit(jax.jit(both), h, p_kda)
    ry, rh, rp = reference.kda_grads(p_kda, h, probe, **kda_kwargs(kw),
                                     **wrong)
    y, dh, dp = got.result()
    errs = {"y": _mixer_err(y, ry), "dh": _rel_err(dh, rh)}
    errs.update({"d" + leaf: _rel_err(dp[leaf], rp[leaf]) for leaf in rp})
    return errs


def check_kda(ctx, cfg, conf, reference, params, mixer_in, kw) -> None:
    """Comparison 2, on the period's last KDA layer."""
    tol = conf["reference_check"]
    kinds = list(cfg.kinds)
    i = len(kinds) - 1 - kinds[::-1].index(KDA)
    p = moved(ctx.seed, i, list(reference.layers(params, len(kinds)))[i][
        "kda_attn"])
    h = two_rows(mixer_in[i]).astype(cfg.dtype)
    grads = read_kda_grads(ctx, cfg, reference, p, h, i, kw)
    err = grads.pop("y")
    ctx.log(f"KDA check: layer {i} |program - reference| / |reference| "
            f"{err:.5f} over two rows")
    ctx.check(np.isfinite(err) and err <= tol["kda_rel_tol"],
              f"layer {i}: the Kimi-Delta-Attention mixer's output differs "
              f"from the reference's recurrence by {err:.5f} of its norm, "
              f"more than {tol['kda_rel_tol']}")
    ctx.log(f"KDA gradient check: layer {i} " + " ".join(
        f"{k} {v:.5f}" for k, v in grads.items()))
    # the gradients that reach the leaves THROUGH the log-decays are sums of
    # differences of rounded decayed products and read wider in bf16
    for what, names, limit in (
            ("a gradient", set(grads) - DECAY_SIDE, "kda_grad_rel_tol"),
            ("a gradient through the log-decays", DECAY_SIDE,
             "kda_decay_grad_rel_tol")):
        err = max(grads[n] for n in names)
        ctx.check(np.isfinite(err) and err <= tol[limit],
                  f"layer {i}: {what} of the Kimi-Delta-Attention mixer "
                  f"differs from the reference's by {err:.5f} of its norm, "
                  f"more than {tol[limit]}: {grads}")


def read_attention(cfg, reference, p_attn, h, kw, **wrong) -> float:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaLatentAttention

    module = LlamaLatentAttention(cfg)
    pos = jnp.arange(h.shape[1])[None, :]
    got = _BESIDE.submit(jax.jit(
        lambda p, h: module.apply({"params": p}, h, pos, None)), p_attn, h)
    want = reference.attention(h, p_attn, **attn_kwargs(kw), **wrong)
    return _rel_err(got.result(), want)


def check_attention(ctx, cfg, conf, reference, params, mixer_in, kw) -> None:
    """Comparison 3: the latent-attention layer alone (bf16 projections, the
    latent norm moved, interleaved rotary on the rope channels and the one
    shared key, the two-product flash kernels, the gate a head)."""
    tol = conf["reference_check"]["attention_rel_tol"]
    kinds = list(cfg.kinds)
    i = kinds.index(FULL)
    p = moved(ctx.seed, i, list(reference.layers(params, len(kinds)))[i][
        "self_attn"])
    err = read_attention(cfg, reference, p, mixer_in[i].astype(cfg.dtype), kw)
    ctx.log(f"attention check: layer {i} (latent, a gate a head) |program - "
            f"reference| / |reference| {err:.5f}")
    ctx.check(np.isfinite(err) and err <= tol,
              f"layer {i}: the latent attention's output differs from the "
              f"reference's by {err:.5f} of its norm, more than {tol}")


def read_experts(seed, cfg, reference, leaves, ffn_in, kw, **wrong) -> tuple:
    """Comparison 4: ``(one error a sparse block, the share of the pairs
    the group limit moved a block)``."""
    import jax

    from deepspeed_tpu.parallel.moe import MoELayer

    layer = MoELayer(cfg.moe, model_dim=cfg.hidden_size,
                     hidden_dim=cfg.expert_size, dtype=cfg.dtype)
    run = jax.jit(lambda p, h: layer.apply({"params": p}, h)[0])
    errs, changed = [], []
    for i in range(cfg.num_dense_layers, len(leaves)):
        h = ffn_in[i].astype(cfg.dtype)
        p = dict(leaves[i]["moe"])
        p["gate"] = dict(p["gate"], expert_bias=seeded_bias(seed, i, p, h))
        got = _BESIDE.submit(run, p, h)
        want, moved_share = reference.sparse_ffn(
            p, h, **dict(route_kwargs(kw), **wrong), with_changed=True)
        errs.append(_rel_err(got.result(), want))
        changed.append(moved_share)
    return errs, changed


def check_experts(ctx, cfg, conf, reference, leaves, ffn_in, kw) -> None:
    tol = conf["reference_check"]["expert_rel_tol"]
    errs, changed = read_experts(ctx.seed, cfg, reference, leaves, ffn_in, kw)
    ctx.log("expert check: |MoE layer - reference FFN| / |reference FFN| a "
            "layer, under a seeded bias " + " ".join(
                f"{e:.5f}" for e in errs) + "; the group limit moved "
            + " ".join(f"{100 * c:.2f}%" for c in changed)
            + " of the pairs")
    ctx.check(max(errs) <= tol and all(np.isfinite(errs)),
              f"an expert layer's output differs from the reference's sparse "
              f"FFN by {max(errs):.5f} of its norm, more than {tol}")
    ctx.check(cfg.moe.n_group == 1 or min(changed) > 0,
              f"the group limit moved no pair of some layer ({changed}): "
              f"the comparison did not see it bind")


def read_loss(engine, ids) -> float:
    return float(engine.eval_batch({"input_ids": ids, "labels": ids}))


def check_reference(ctx, engine, cfg, conf, reference, batches) -> float:
    """Comparisons 1 to 5; returns the engine's loss."""
    tol = conf["reference_check"]
    ids = next(batches)["input_ids"][:engine.dp_world]
    params = engine.state.params
    kw = reference_kwargs(conf)
    lam = float(conf["model_options"].get("mtp_loss_weight", 0.0))
    got = _BESIDE.submit(read_loss, engine, ids)
    mixer_in, ffn_in = [], []
    main, second = reference.loss_parts(params, ids, mixer_inputs=mixer_in,
                                        ffn_inputs=ffn_in, **kw)
    # as soon as the reference's forward has its inputs, and before the
    # evaluation step has finished compiling
    mixer = _ASIDE.submit(check_kda, ctx, cfg, conf, reference, params,
                          mixer_in, kw)
    want, got = float(main) + lam * float(second), got.result()
    ctx.log(f"reference check: engine loss {got:.6f}  reference {want:.6f} "
            f"(main {float(main):.6f} + {lam} x second {float(second):.6f})  "
            f"difference {got - want:+.6f}")
    ctx.check(abs(got - want) <= tol["loss_abs_tol"],
              f"eval loss {got} differs from the reference {want} by more "
              f"than {tol['loss_abs_tol']}")
    leaves = list(reference.layers(params, cfg.num_hidden_layers))
    check_attention(ctx, cfg, conf, reference, params, mixer_in, kw)
    check_experts(ctx, cfg, conf, reference, leaves, ffn_in, kw)
    check_dense(ctx, cfg, conf, reference, params, ffn_in)
    mixer.result()
    return got


def scope_split(ctx, engine, batches) -> dict:
    """Device ms a step under each ``linear_attn/`` scope, under the six
    together and of the whole step, from a short profiler session of its
    own after the window (``engine.profile_device_scopes``)."""
    table = engine.profile_device_scopes(batches, steps=4, depth=4)
    out = {"step": table["device_ms_a_step"]}
    for scope in SCOPES:
        out[scope] = sum(r["ms_a_step"] for r in table["scopes"]
                         if scope in r["scope"])
    out["linear_attn"] = sum(out[scope] for scope in SCOPES)
    ctx.log("device ms a step under " + ", ".join(
        f"{scope} {out[scope]:.3f}" for scope in SCOPES)
        + f" of {out['step']:.3f}")
    rest = sorted((r for r in table["scopes"]
                   if not any(scope in r["scope"] for scope in SCOPES)),
                  key=lambda r: -r["ms_a_step"])
    ctx.log("and under the other scopes (ms a step): " + ", ".join(
        f"{r['scope']} {r['ms_a_step']:.2f}" for r in rest[:40]))
    return out


def decay_channels(snapshot: dict):
    """The program's gauge of the log-decays a head a position under which
    the delta rule's last traced pass ran (1: a decay a head; the key
    head's channels: a decay a key channel), or ``None`` without it."""
    entry = snapshot.get(DECAY_GAUGE)
    return entry["samples"][0]["value"] if entry and entry["samples"] \
        else None


def check_delta_rule(ctx, conf, report, channels) -> str:
    """The mechanism the cell's ``why`` names, and no implementation of it:
    site ``gated_delta`` of ``report`` (``dispatch_report()``'s rows that
    count) resolved, to a kernel or to XLA's program, and it ran under
    ``expect_gated_delta_decay_channels`` log-decays a head, the key head's
    channels (one decay a head, Gated DeltaNet's rule, is the fault that
    comparison 2 reads at 0.393).  Returns what ran in words, for the
    result line."""
    ran = [r for r in report if r[0] == "gated_delta"]
    ctx.check(bool(ran), "the delta rule never resolved: site gated_delta "
                         f"is in no row of {sorted(r[:2] for r in report)}")
    want = conf["expect_gated_delta_decay_channels"]
    ctx.check(channels == want,
              f"the delta rule ran under {channels} log-decays a head a "
              f"position (gauge {DECAY_GAUGE}), not the {want} of a decay a "
              f"key channel")
    said = "; ".join(f"{impl} x {n} ({reason})"
                     for _, impl, reason, n in ran) or "never resolved"
    ctx.log(f"the delta rule under {channels} log-decays a head ran as {said}")
    return said


def run(ctx, reference) -> dict:
    """``train_lm.run`` with this module's comparison in place of its own,
    the engine kept for the comparison after the window and for the scopes,
    and every step's counts kept as the program books them."""
    import dataclasses
    import importlib

    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report
    from deepspeed_tpu.parallel import moe

    if "kda_lower_bound" not in {
            f.name for f in dataclasses.fields(LlamaConfig)}:
        sys.exit("benchmark: this program's LlamaConfig has no kda_attention "
                 "layer type (kda_lower_bound): it cannot run a Kimi-Delta-"
                 f"Attention block ({ctx.cell.name})")
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
    rows = {r[:2] for r in report}
    if not ctx.rehearse:
        flash = sum(n for s, i, r, n in report
                    if (s, i) == ("attention", "flash") and MLA_REASON in r)
        ctx.check(flash >= 1, f"the two-product flash kernels never ran the "
                              f"latent-attention layer: {report}")
        xla = [r for r in report if r[:2] == ("attention", "jnp")]
        ctx.check(not xla, f"attention took the XLA path: {xla}")
        want = conf.get("expect_moe_rows_impl")
        ctx.check(want is None or ("moe_rows", want) in rows,
                  f"moe_rows never resolved to {want}: {sorted(rows)}")
    train_mellum2.count_what_was_routed_here(ctx, out)
    obs = out["observed"]
    obs["gated_delta_impl"] = check_delta_rule(
        ctx, conf, report, decay_channels(_program.registry_snapshot()))
    flops = importlib.import_module("benchmark." + conf["flops"])
    step_tokens = obs["tokens"] // max(obs["steps"], 1) // obs["n_devices"]
    obs["kda_flops_per_step"] = flops.kda_flops_per_step(conf, step_tokens)
    obs["kda_bytes_per_step"] = flops.kda_bytes_per_step(conf, step_tokens)
    if ctx.trace and not ctx.rehearse:
        from benchmark import loadgen

        batches = loadgen.packed_batches(
            ctx.sized(ctx.cell.traffic), ctx.seed + 1,
            engine.train_batch_size, cfg.vocab_size)
        obs["device_scope_ms"] = scope_split(ctx, engine, batches)
    return out
