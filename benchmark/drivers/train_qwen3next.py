"""``drivers/train_lm.py`` for Qwen3-Next: the same engine, data, window,
fences and ``observed`` keys (its ``run``, unchanged), with the set-up's
comparison against the plain reference widened to what this model's loss
cannot see, and the device's time by the program's ``linear_attn/`` scopes
for the two readers this cell brings.

Before the window, on one seeded row a rank:

1. ``eval_batch`` against the reference's loss (cross-entropy + the
   weighted load-balancing loss);

and, **under seeded norm weights, ``A_log`` and ``dt_bias``** (every 1-D
leaf of the layer moved off its initial value by the seed: at ``w = 0`` a
zero-centred norm and a plain one from ones are the same function):

2. the first Gated DeltaNet mixer alone (``models/llama.py GatedDeltaNet``
   in bf16, the chunked delta rule) against ``reference.linear_attention``
   (float32, the recurrence one position a step) on the reference
   forward's normalised hidden states, over TWO rows (the seeded row and
   the same row read backwards) and over the first 64 positions of each
   row alone, so that a row's state leaking into the next row shows; and
   the gradients of a seeded scalar of that output
   with respect to the input and every leaf (the backward is new code too);
3. the gated attention layer alone against ``reference.attention``;
4. every expert layer alone (router, top-10, the held experts, the gated
   shared expert) against ``reference.expert_ffn`` with the same share.

After it (``train_mellum2``'s: this model's routing is its code):

5. the sorted dispatch moved its rows with the Pallas row kernels and the
   delta rule resolved to what the file expects.
"""
from __future__ import annotations

import concurrent.futures
import sys

import numpy as np

from benchmark.drivers import train_lm, train_mellum2, train_trinity

FAMILIES = train_lm.FAMILIES
_rel_err = train_mellum2._rel_err
model_config = train_lm.model_config
reference_kwargs = train_mellum2.reference_kwargs
LINEAR, FULL = "linear_attention", "full_attention"
HEAD_POSITIONS = 64     # of each row, read alone by the mixer's check
SCOPES = ("linear_attn/in_proj", "linear_attn/conv", "linear_attn/delta_rule",
          "linear_attn/gated_norm", "linear_attn/out_proj")
# the program's side of a comparison is staged and run on this thread while
# the reference's compiles on the caller's (``train_sdar.py``'s way: XLA
# compiles outside the interpreter's lock; from an empty compile cache the
# two sides of the five comparisons are ~80 s of a set-up that has 360 s
# with its window)
_BESIDE = concurrent.futures.ThreadPoolExecutor(1)


def two_rows(h):
    """``h`` (1, S, E) and the same row read backwards."""
    return np.concatenate([np.asarray(h), np.asarray(h)[:, ::-1]], axis=0)


def moved(seed: int, layer: int, tree):
    """``tree`` with every 1-D leaf (norm weights, ``dt_bias``, the shared
    expert's gate) moved by seeded normal noise of 0.2, and ``A_log`` drawn
    anew as ``log U(0.005, 0.5)``: the comparisons read functions, not
    initial values, and at ``A ~ U(0, 16)`` nearly every head forgets its
    state within a token or two, so a state reset at a chunk's edge or
    leaking into the next row would read sound."""
    import jax

    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 48, layer])

    def one(path, x):
        if x.ndim != 1:     # a matrix stays where it is (GBs, on the device)
            return x
        x = np.asarray(x)
        if jax.tree_util.keystr(path).endswith("['A_log']"):
            return np.log(rng.uniform(0.005, 0.5, x.shape)).astype(x.dtype)
        return (x + rng.normal(0.0, 0.2, x.shape)).astype(x.dtype)

    return jax.tree_util.tree_map_with_path(one, tree)


def _mixer_err(got, want) -> float:
    """The larger of two readings of the mixer's output against the
    reference's, each a share of the reference's norm there: over
    everything, and over the first :data:`HEAD_POSITIONS` of each row alone
    - where a state that starts from anything but zeros (the row before's)
    is most of the output and, over 16,384 positions, a hundredth of it."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    n = HEAD_POSITIONS
    return max(_rel_err(got, want), _rel_err(got[:, :n], want[:, :n]))


def linear_kwargs(cfg) -> dict:
    return {"n_k_heads": cfg.linear_num_key_heads,
            "n_v_heads": cfg.linear_num_value_heads, "eps": cfg.rms_norm_eps}


def read_linear(cfg, reference, p_lin, h, **wrong) -> float:
    """Error of the program's DeltaNet mixer against the reference's, as a
    share of the reference's norm."""
    import jax

    from deepspeed_tpu.models.llama import GatedDeltaNet

    module = GatedDeltaNet(cfg)
    got = _BESIDE.submit(
        jax.jit(lambda p, h: module.apply({"params": p}, h)), p_lin, h)
    want = reference.linear_attention(p_lin, h, **linear_kwargs(cfg), **wrong)
    return _mixer_err(got.result(), want)


def read_linear_grads(ctx, cfg, reference, p_lin, h, layer: int,
                      **wrong) -> dict:
    """``{"y": ..., "dh": ..., "d<leaf>": ...}``: relative error of the
    mixer's output and of d(sum(y * probe)) / d(h, each leaf), program
    against reference, under a seeded probe; one executable a side (the
    set-up pays for each)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import GatedDeltaNet

    module = GatedDeltaNet(cfg)
    rng = np.random.default_rng([int(ctx.seed) & 0xFFFFFFFF, 49, layer])
    probe = rng.standard_normal(h.shape).astype(np.float32)

    def both(h, p):
        y, pull = jax.vjp(lambda h, p: module.apply({"params": p}, h), h, p)
        return (y,) + pull(jnp.asarray(probe, y.dtype))

    got = _BESIDE.submit(jax.jit(both), h, p_lin)
    ry, rh, rp = reference.linear_attention_grads(
        p_lin, h, probe, **linear_kwargs(cfg), **wrong)
    y, dh, dp = got.result()
    errs = {"y": _mixer_err(y, ry), "dh": _rel_err(dh, rh)}
    errs.update({"d" + leaf: _rel_err(dp[leaf], rp[leaf]) for leaf in rp})
    return errs


def check_linear(ctx, cfg, conf, reference, params, hidden) -> None:
    """Comparison 2, on the first DeltaNet layer."""
    tol = conf["reference_check"]
    i = list(cfg.kinds).index(LINEAR)
    p = moved(ctx.seed, i, list(reference.layers(params, len(hidden)))[i][
        "linear_attn"])
    h = two_rows(hidden[i]).astype(cfg.dtype)
    grads = read_linear_grads(ctx, cfg, reference, p, h, i)
    err = grads.pop("y")
    ctx.log(f"linear attention check: layer {i} |program - reference| / "
            f"|reference| {err:.5f} over two rows")
    ctx.check(np.isfinite(err) and err <= tol["linear_attn_rel_tol"],
              f"layer {i}: the Gated DeltaNet mixer's output differs from "
              f"the reference's recurrence by {err:.5f} of its norm, more "
              f"than {tol['linear_attn_rel_tol']}")
    ctx.log(f"linear attention gradient check: layer {i} " + " ".join(
        f"{k} {v:.5f}" for k, v in grads.items()))
    err = max(grads.values())
    ctx.check(np.isfinite(err) and err <= tol["linear_attn_grad_rel_tol"],
              f"layer {i}: a gradient of the Gated DeltaNet mixer differs "
              f"from the reference's by {err:.5f} of its norm, more than "
              f"{tol['linear_attn_grad_rel_tol']}: {grads}")


def read_attention(cfg, reference, p_attn, h, conf, **wrong) -> float:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaAttention

    module = LlamaAttention(cfg, FULL)
    pos = jnp.arange(h.shape[1])[None, :]
    got = _BESIDE.submit(jax.jit(
        lambda p, h: module.apply({"params": p}, h, pos, None)), p_attn, h)
    want = reference.attention(
        FULL, p_attn, h, n_head=cfg.num_attention_heads,
        n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
        rope_theta=float(conf["rope_theta"]),
        partial_rotary_factor=conf["partial_rotary_factor"],
        eps=cfg.rms_norm_eps, **wrong)
    return _rel_err(got.result(), want)


def check_attention(ctx, cfg, conf, reference, params, hidden) -> None:
    """Comparison 3: the first attention layer alone (bf16 compute, the
    zero-centred per-head norm, the partial rotation, grouped queries
    through the flash kernel at 256 lanes a head, the output gate)."""
    tol = conf["reference_check"]["attention_rel_tol"]
    i = list(cfg.kinds).index(FULL)
    p = moved(ctx.seed, i, list(reference.layers(params, len(hidden)))[i][
        "self_attn"])
    err = read_attention(cfg, reference, p, hidden[i].astype(cfg.dtype), conf)
    ctx.log(f"attention check: layer {i} ({FULL}) |program - reference| / "
            f"|reference| {err:.5f}")
    ctx.check(np.isfinite(err) and err <= tol,
              f"layer {i}: the attention layer's output differs from the "
              f"reference's by {err:.5f} of its norm, more than {tol}")


def read_experts(ctx, cfg, conf, reference, params, hidden, **wrong) -> list:
    """Each expert layer's error, the shared expert's gate moved."""
    import jax

    from deepspeed_tpu.parallel.moe import MoELayer

    layer = MoELayer(cfg.moe, model_dim=cfg.hidden_size,
                     hidden_dim=cfg.expert_size, dtype=cfg.dtype)
    run = jax.jit(lambda p, h: layer.apply({"params": p}, h)[0])
    errs = []
    for i, p in enumerate(reference.layers(params, len(hidden))):
        h = hidden[i].astype(cfg.dtype)
        p = moved(ctx.seed, i, p["moe"])
        got = _BESIDE.submit(run, p, h)
        want = reference.expert_ffn(
            p, h, top_k=conf["num_experts_per_tok"],
            first_expert=cfg.moe.first_expert, **wrong)
        errs.append(_rel_err(got.result(), want))
    return errs


def check_experts(ctx, cfg, conf, reference, params, hidden) -> None:
    tol = conf["reference_check"]["expert_rel_tol"]
    errs = read_experts(ctx, cfg, conf, reference, params, hidden)
    ctx.log("expert check: |MoE layer - reference FFN| / |reference FFN| a "
            "layer " + " ".join(f"{e:.5f}" for e in errs))
    ctx.check(max(errs) <= tol and all(np.isfinite(errs)),
              f"an expert layer's output differs from the reference's sparse "
              f"FFN by {max(errs):.5f} of its norm, more than {tol}")


def check_reference(ctx, engine, cfg, conf, reference, batches) -> float:
    """Comparisons 1 to 4; returns the engine's loss."""
    tol = conf["reference_check"]
    rows = engine.dp_world
    ids = next(batches)["input_ids"][:rows]
    got = _BESIDE.submit(engine.eval_batch, {"input_ids": ids, "labels": ids})
    ffn_in, mixer_in = [], []
    ce, aux = reference.loss_parts(
        engine.state.params, ids, **reference_kwargs(conf),
        ffn_inputs=ffn_in, mixer_inputs=mixer_in)
    want, got = float(ce) + float(aux), float(got.result())
    ctx.log(f"reference check: engine loss {got:.6f}  reference {want:.6f} "
            f"(cross-entropy {float(ce):.6f} + router loss {float(aux):.6f})"
            f"  difference {got - want:+.6f}")
    ctx.check(abs(got - want) <= tol["loss_abs_tol"],
              f"eval loss {got} differs from the reference {want} by more "
              f"than {tol['loss_abs_tol']}")
    params = engine.state.params
    check_linear(ctx, cfg, conf, reference, params, mixer_in)
    check_attention(ctx, cfg, conf, reference, params, mixer_in)
    check_experts(ctx, cfg, conf, reference, params, ffn_in)
    return got


def scope_split(ctx, engine, batches) -> dict:
    """Device ms a step under each ``linear_attn/`` scope, under the five
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


def run(ctx, reference) -> dict:
    """``train_lm.run`` with this module's comparison in place of its own
    and the engine kept for the scopes."""
    import dataclasses
    import importlib

    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    if "linear_num_value_heads" not in {
            f.name for f in dataclasses.fields(LlamaConfig)}:
        sys.exit("benchmark: this program's LlamaConfig has no "
                 "linear_attention layer type (linear_num_value_heads): it "
                 f"cannot run a Gated DeltaNet block ({ctx.cell.name})")
    built = []

    def build(ctx):
        built.append(theirs["build"](ctx))
        return built[-1]

    with train_trinity._in_place_of(
            train_lm, build=build, check_reference=check_reference) as theirs:
        out = train_lm.run(ctx, reference)
    engine, cfg, conf = built[-1]
    rows = {(s, i) for s, i, _, n in dispatch_report() if n}
    for site in ("moe_rows", "gated_delta"):
        want = conf.get(f"expect_{site}_impl")
        ctx.check(ctx.rehearse or want is None or (site, want) in rows,
                  f"{site} never resolved to {want}: {sorted(rows)}")
    train_mellum2.count_what_was_routed_here(ctx, out)
    obs = out["observed"]
    flops = importlib.import_module("benchmark." + conf["flops"])
    step_tokens = obs["tokens"] // max(obs["steps"], 1) // obs["n_devices"]
    obs["gated_delta_flops_per_step"] = \
        flops.gated_delta_flops_per_step(conf, step_tokens)
    obs["gated_delta_bytes_per_step"] = \
        flops.gated_delta_bytes_per_step(conf, step_tokens)
    if ctx.trace and not ctx.rehearse:
        from benchmark import loadgen

        batches = loadgen.packed_batches(
            ctx.sized(ctx.cell.traffic), ctx.seed + 1,
            engine.train_batch_size, cfg.vocab_size)
        obs["device_scope_ms"] = scope_split(ctx, engine, batches)
    return out
