"""``drivers/train_lm.py`` for Olmo-Hybrid: the same engine, data, window,
fences and ``observed`` keys (its ``run``, unchanged), with the set-up's
comparison against the plain reference widened to what this model's loss
cannot see, and the device's time by the program's ``linear_attn/`` and
``mlp_dense`` scopes for the readers this cell reports.

Before the window, on one seeded row a rank:

a. ``eval_batch`` against the reference's loss (cross-entropy alone);

and, **under seeded 1-D leaves** (:func:`moved`: every norm weight and
``dt_bias`` moved off its initial value, ``A_log`` drawn anew as ``log
U(0.005, 0.5)`` so that states live for hundreds of tokens, and the ``b``
columns of ``in_proj_ba`` rescaled so that ``b`` has the file's standard
deviation over the comparison's rows, which puts a stated share of ``beta =
2 sigmoid(b)`` above 1.5: a program that forgot the factor 2, or an inverse
that loses accuracy near 2, would otherwise read sound):

b. the last Gated DeltaNet mixer of the period alone (``models/llama.py
   GatedDeltaNet`` in bf16, the chunked delta rule over lane slots)
   against ``reference.linear_attention`` (float32, the recurrence one
   position a step) on the reference forward's own input to that mixer -
   under the reordered norm the residual stream itself, rounded to bf16 -
   over TWO rows (the seeded row and the same row read backwards) and over
   the first 64 positions of each row alone, and the gradients of a seeded
   scalar of that output with respect to the input and every leaf;
c. the attention mixer alone (whole-projection q and k norms, no rotation,
   flash at 30 heads on 30) against ``reference.attention``;
d. the dense SwiGLU of that block alone, ``LlamaBlock._dense_ffn`` on the
   block's own leaves (the method the window times under ``mlp_dense``),
   against ``reference.dense_ffn``;
e. the reordered norm: that whole block (``LlamaBlock``) from the
   reference's ``x`` against ``reference.block``.

After the window: flash, the Pallas delta rule and the Pallas filter
resolved to what the file expects.
"""
from __future__ import annotations

import concurrent.futures
import sys

import numpy as np

from benchmark.drivers import train_lm, train_mellum2, train_trinity

FAMILIES = train_lm.FAMILIES
_rel_err = train_mellum2._rel_err
model_config = train_lm.model_config
reference_kwargs = train_lm.reference_kwargs
LINEAR, FULL = "linear_attention", "full_attention"
HEAD_POSITIONS = 64     # of each row, read alone by the mixer's check
SCOPES = ("linear_attn/in_proj", "linear_attn/conv", "linear_attn/delta_rule",
          "linear_attn/gated_norm", "linear_attn/out_proj")
DENSE_SCOPE = "mlp_dense"
# the program's side of a comparison is staged and run on this thread while
# the reference's compiles on the caller's (``train_qwen3next.py``'s way)
_BESIDE = concurrent.futures.ThreadPoolExecutor(1)


def two_rows(h):
    """``h`` (1, S, E) and the same row read backwards."""
    return np.concatenate([np.asarray(h), np.asarray(h)[:, ::-1]], axis=0)


def moved(seed: int, layer: int, tree, beta_stretch: float = 1.0):
    """``tree`` with every 1-D leaf (norm weights, ``dt_bias``) moved by
    seeded normal noise of 0.2, ``A_log`` drawn anew as ``log U(0.005,
    0.5)`` and the ``b`` half of ``in_proj_ba`` times ``beta_stretch``: the
    comparisons read functions, not initial values."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 52, layer])

    def one(path, x):
        name = jax.tree_util.keystr(path)
        if name.endswith("['in_proj_ba_kernel']") and beta_stretch != 1.0:
            half = x.shape[1] // 2          # [b | a]
            return jnp.asarray(x).at[:, :half].multiply(beta_stretch)
        if x.ndim != 1:     # a matrix stays where it is (on the device)
            return x
        x = np.asarray(x)
        if name.endswith("['A_log']"):
            return np.log(rng.uniform(0.005, 0.5, x.shape)).astype(x.dtype)
        return (x + rng.normal(0.0, 0.2, x.shape)).astype(x.dtype)

    return jax.tree_util.tree_map_with_path(one, tree)


def _mixer_err(got, want) -> float:
    """The larger of two readings of the mixer's output against the
    reference's, each a share of the reference's norm there: over
    everything, and over the first :data:`HEAD_POSITIONS` of each row alone
    (where a state that starts from anything but zeros is most of the
    output)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    n = HEAD_POSITIONS
    return max(_rel_err(got, want), _rel_err(got[:, :n], want[:, :n]))


def linear_kwargs(cfg) -> dict:
    return {"n_k_heads": cfg.linear_num_key_heads,
            "n_v_heads": cfg.linear_num_value_heads,
            "key_dim": cfg.linear_key_head_dim, "eps": cfg.rms_norm_eps}


def head_kwargs(cfg) -> dict:
    return {"n_head": cfg.num_attention_heads, "n_kv_head": cfg.kv_heads,
            "head_dim": cfg.head_dim}


def _b(p_lin, h, cfg):
    """``b = h W_b`` (float32), what ``beta = 2 sigmoid(b)`` is made of."""
    return np.asarray(h, np.float32) @ np.asarray(
        p_lin["in_proj_ba_kernel"][:, :cfg.linear_num_value_heads],
        np.float32)


def beta_high_share(p_lin, h, cfg) -> float:
    """The share of ``beta = 2 sigmoid(h W_b)`` above 1.5."""
    return float((2.0 / (1.0 + np.exp(-_b(p_lin, h, cfg))) > 1.5).mean())


def linear_operands(seed: int, cfg, conf, leaves, block_in):
    """``(layer, leaves, input)`` of comparison b: the last DeltaNet layer
    of the period, its 1-D leaves moved and ``b`` rescaled to the file's
    standard deviation, on two rows in the compute type."""
    i = max(j for j, kind in enumerate(cfg.kinds) if kind == LINEAR)
    h = two_rows(block_in[i]).astype(cfg.dtype)
    p = leaves[i]["linear_attn"]
    stretch = float(conf["reference_check"]["b_std"]) / _b(p, h, cfg).std()
    return i, moved(seed, i, p, stretch), h


def read_linear(cfg, reference, p_lin, h, **wrong) -> float:
    """Error of the program's DeltaNet mixer against the reference's, as a
    share of the reference's norm (forward alone)."""
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
    against reference, under a seeded probe; one executable a side."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import GatedDeltaNet

    module = GatedDeltaNet(cfg)
    rng = np.random.default_rng([int(ctx.seed) & 0xFFFFFFFF, 53, layer])
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


def check_linear(ctx, cfg, conf, reference, leaves, hidden) -> None:
    """Comparison b, on the last DeltaNet layer of the period."""
    tol = conf["reference_check"]
    i, p, h = linear_operands(ctx.seed, cfg, conf, leaves, hidden)
    share = beta_high_share(p, h, cfg)
    ctx.log(f"linear attention check: layer {i}, {100 * share:.1f}% of beta "
            f"above 1.5")
    ctx.check(share >= tol["beta_high_share_min"],
              f"layer {i}: {share:.3f} of beta lies above 1.5 in the "
              f"comparison, less than {tol['beta_high_share_min']}")
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


def read_attention(cfg, reference, p_attn, h, **wrong) -> float:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaAttention

    module = LlamaAttention(cfg, FULL)
    pos = jnp.arange(h.shape[1])[None, :]
    got = _BESIDE.submit(jax.jit(
        lambda p, h: module.apply({"params": p}, h, pos, None)), p_attn, h)
    want = reference.attention(FULL, p_attn, h, **head_kwargs(cfg),
                               eps=cfg.rms_norm_eps, **wrong)
    return _rel_err(got.result(), want)


def read_dense(cfg, reference, p_layer, h, kind, **wrong) -> float:
    """``LlamaBlock._dense_ffn`` on the block's own leaves: a block whose
    ``__call__`` is that method and nothing else
    (``train_trinity.check_dense``'s way)."""
    import flax.linen as nn
    import jax

    from deepspeed_tpu.models.llama import LlamaBlock

    class DenseFFN(LlamaBlock):
        @nn.compact
        def __call__(self, h):
            return self._dense_ffn(h)

    ffn = DenseFFN(cfg, kind=kind)
    p = {k: p_layer[k] for k in ("gate_proj_kernel", "up_proj_kernel",
                                 "down_proj_kernel")}
    got = _BESIDE.submit(jax.jit(lambda p, h: ffn.apply({"params": p}, h)),
                         p, h)
    return _rel_err(got.result(), reference.dense_ffn(p_layer, h, **wrong))


def read_block(cfg, reference, p_layer, x, kind, **wrong) -> float:
    """One whole ``LlamaBlock`` from ``x`` against ``reference.block``."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaBlock

    module = LlamaBlock(cfg, kind=kind)
    pos = jnp.arange(x.shape[1])[None, :]
    got = _BESIDE.submit(jax.jit(
        lambda p, x: module.apply({"params": p}, x, (pos, None))[0]),
        p_layer, x.astype(cfg.dtype))
    # the reference reads the same rounded stream, in float32
    want = reference.block(
        p_layer, x.astype(cfg.dtype), kind=kind, **linear_kwargs(cfg),
        **head_kwargs(cfg), **wrong)
    return _rel_err(got.result(), want)


def check_attention_block(ctx, cfg, conf, reference, leaves, block_in,
                          ffn_in) -> None:
    """Comparisons c, d and e, on the attention block of the period."""
    tol = conf["reference_check"]
    i = list(cfg.kinds).index(FULL)
    p = moved(ctx.seed, i, leaves[i])
    err = read_attention(cfg, reference, p["self_attn"],
                         block_in[i].astype(cfg.dtype))
    ctx.log(f"attention check: layer {i} ({FULL}) |program - reference| / "
            f"|reference| {err:.5f}")
    ctx.check(np.isfinite(err) and err <= tol["attention_rel_tol"],
              f"layer {i}: the attention layer's output differs from the "
              f"reference's by {err:.5f} of its norm, more than "
              f"{tol['attention_rel_tol']}")
    err = read_dense(cfg, reference, p, ffn_in[i].astype(cfg.dtype), FULL)
    ctx.log(f"dense check: layer {i} |program - reference| / |reference| "
            f"{err:.5f}")
    ctx.check(np.isfinite(err) and err <= tol["dense_rel_tol"],
              f"layer {i}: the dense SwiGLU's output differs from the "
              f"reference's by {err:.5f} of its norm, more than "
              f"{tol['dense_rel_tol']}")
    err = read_block(cfg, reference, p, block_in[i], FULL)
    ctx.log(f"block check: layer {i} (the reordered norm) |program - "
            f"reference| / |reference| {err:.5f}")
    ctx.check(np.isfinite(err) and err <= tol["block_rel_tol"],
              f"layer {i}: the whole block's output differs from the "
              f"reference's by {err:.5f} of its norm, more than "
              f"{tol['block_rel_tol']}")


def check_reference(ctx, engine, cfg, conf, reference, batches) -> float:
    """Comparisons a to e; returns the engine's loss."""
    tol = conf["reference_check"]
    rows = engine.dp_world
    ids = next(batches)["input_ids"][:rows]
    got = _BESIDE.submit(engine.eval_batch, {"input_ids": ids, "labels": ids})
    block_in, ffn_in = [], []
    want, _ = reference.loss_parts(
        engine.state.params, ids, **reference_kwargs(conf),
        block_inputs=block_in, ffn_inputs=ffn_in)
    want, got = float(want), float(got.result())
    ctx.log(f"reference check: engine loss {got:.6f}  reference {want:.6f} "
            f"(cross-entropy alone)  difference {got - want:+.6f}")
    ctx.check(abs(got - want) <= tol["loss_abs_tol"],
              f"eval loss {got} differs from the reference {want} by more "
              f"than {tol['loss_abs_tol']}")
    leaves = list(reference.layers(engine.state.params, len(block_in)))
    check_linear(ctx, cfg, conf, reference, leaves, block_in)
    check_attention_block(ctx, cfg, conf, reference, leaves, block_in, ffn_in)
    return got


def scope_split(ctx, engine, batches) -> dict:
    """Device ms a step under each ``linear_attn/`` scope, under the five
    together, under ``mlp_dense`` and of the whole step, from a short
    profiler session of its own after the window
    (``engine.profile_device_scopes``)."""
    table = engine.profile_device_scopes(batches, steps=4, depth=4)
    out = {"step": table["device_ms_a_step"]}
    for scope in SCOPES + (DENSE_SCOPE,):
        out[scope] = sum(r["ms_a_step"] for r in table["scopes"]
                         if scope in r["scope"])
    out["linear_attn"] = sum(out[scope] for scope in SCOPES)
    ctx.log("device ms a step under " + ", ".join(
        f"{scope} {out[scope]:.3f}" for scope in SCOPES + (DENSE_SCOPE,))
        + f" of {out['step']:.3f}")
    rest = sorted((r for r in table["scopes"] if not any(
        scope in r["scope"] for scope in SCOPES + (DENSE_SCOPE,))),
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

    if "reordered_norm" not in {
            f.name for f in dataclasses.fields(LlamaConfig)}:
        sys.exit("benchmark: this program's LlamaConfig has no "
                 "reordered_norm field: it cannot run an Olmo-Hybrid block "
                 f"({ctx.cell.name})")
    built = []

    def build(ctx):
        built.append(theirs["build"](ctx))
        return built[-1]

    with train_trinity._in_place_of(
            train_lm, build=build, check_reference=check_reference) as theirs:
        out = train_lm.run(ctx, reference)
    engine, cfg, conf = built[-1]
    rows = {(s, i) for s, i, _, n in dispatch_report() if n}
    for site in ("gated_delta", "short_conv"):
        want = conf.get(f"expect_{site}_impl")
        ctx.check(ctx.rehearse or want is None or (site, want) in rows,
                  f"{site} never resolved to {want}: {sorted(rows)}")
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
