"""Drive the trainer on a decoder named by the configuration file:
``deepspeed_tpu.initialize`` → ``init_params`` → a data iterator of packed
documents → ``train_batch``, for ``--seconds``.

The same window, fences, checks and ``observed`` keys as
``drivers/train.py`` (the GPT-2 family's driver); what differs is data.
The configuration file's ``family`` picks the model and config classes
(:data:`FAMILIES`); every top-level key of the file that is a field of the
config class is passed to it under its own name (the Hugging Face names
are the LLaMA family's field names), ``model_options`` on top; a
``num_experts`` key makes a ``MoEConfig`` from ``num_experts``,
``num_experts_per_tok``, ``norm_topk_prob`` and the ``moe`` section;
``flops`` names the module of required operations; ``reference_args`` maps
the reference's keyword arguments to configuration keys.

Set-up: build the engine, make the weights on the device from the seed
(``init_scale`` of the file, a factor a leaf, is the benchmark's own
departure from the program's initialiser and applied here), compare
``eval_batch`` (cross-entropy plus the router losses) with the plain
reference on one seeded row a rank and, for a sparse model, every MoE
layer alone with the reference's sparse FFN on the same hidden states;
take the warm-up steps.  Window:
``train_batch(data_iter=...)`` back to back, the host allowed
``run_ahead`` steps in front of the device, fenced by
``block_until_ready`` on the last loss.
"""
from __future__ import annotations

import dataclasses
import importlib
import time

import numpy as np

from benchmark import loadgen
from benchmark.layer_metrics import moe_load_imbalance

# family -> (module, config class, model class)
FAMILIES = {
    "llama": ("deepspeed_tpu.models.llama", "LlamaConfig", "LlamaForCausalLM"),
}
DROPPED = "moe_dropped_tokens_total"


def model_config(conf: dict):
    """``(model, config object)`` of a sized configuration dict."""
    module, cfg_cls, model_cls = FAMILIES[conf["family"]]
    mod = importlib.import_module(module)
    cfg_cls, model_cls = getattr(mod, cfg_cls), getattr(mod, model_cls)
    fields = {f.name for f in dataclasses.fields(cfg_cls)}
    kw = {k: v for k, v in conf.items() if k in fields}
    kw.update(conf["model_options"])
    if "num_experts" in conf:
        from deepspeed_tpu.parallel.moe import MoEConfig

        kw["moe"] = MoEConfig(num_experts=conf["num_experts"],
                              top_k=conf["num_experts_per_tok"],
                              norm_topk_prob=conf["norm_topk_prob"],
                              **conf["moe"])
    if "rope_theta" in kw:
        kw["rope_theta"] = float(kw["rope_theta"])
    cfg = cfg_cls(**kw)
    return model_cls(cfg), cfg


def build(ctx):
    import deepspeed_tpu

    conf = ctx.sized(ctx.cell.config)
    model, cfg = model_config(conf)
    ds = dict(conf["engine"])
    ds["train_micro_batch_size_per_gpu"] = conf["micro_per_device"]
    ds["seed"] = ctx.seed % (2**31 - 1)
    ds["steps_per_print"] = 10**9
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds)
    return engine, cfg, conf


def reference_kwargs(conf: dict) -> dict:
    kw = {arg: conf[key] for arg, key in conf["reference_args"].items()}
    kw.update({k: v for k, v in conf.get("moe", {}).items()
               if k in ("aux_loss_weight", "z_loss_weight")})
    return kw


def scale_init(engine, factors: dict) -> None:
    """Multiply top-level leaves of the fresh master weights by the
    configuration file's ``init_scale``.  The departure from the source's
    initialiser belongs to the benchmark, so it is made here, on the
    engine's state, and is no option of the model."""
    state = engine.state
    params = dict(state.params)
    for name, factor in factors.items():
        params[name] = params[name] * factor
    engine._state = state.replace(params=params)


def check_experts(ctx, cfg, conf, reference, params, hidden) -> None:
    """Every MoE layer of the program alone against the reference's sparse
    FFN, on the same input: ``hidden[i]``, the reference forward's
    normalised hidden states before layer i's FFN, rounded to the compute
    type first so that both routers see the same numbers.  Here the
    experts are all of the output (in the loss they are ~3% of the
    residual stream at initialisation): a lost group, a wrong group
    boundary or a grouped matmul in a lower precision shows."""
    import jax

    from deepspeed_tpu.parallel.moe import MoELayer

    layer = MoELayer(cfg.moe, model_dim=cfg.hidden_size,
                     hidden_dim=cfg.intermediate_size, dtype=cfg.dtype)
    run = jax.jit(lambda p, h: layer.apply({"params": p}, h)[0])
    tol = conf["reference_check"]["expert_rel_tol"]
    errs = []
    for p, h in zip(reference.layers(params, len(hidden)), hidden):
        h = h.astype(cfg.dtype)
        got = np.asarray(run(p["moe"], h), np.float32)
        want = np.asarray(reference.expert_ffn(
            p["moe"], h, top_k=conf["num_experts_per_tok"],
            norm_topk_prob=conf["norm_topk_prob"]))
        errs.append(float(np.linalg.norm(got - want) / np.linalg.norm(want)))
    ctx.log("expert check: |MoE layer - reference FFN| / |reference FFN| a "
            "layer " + " ".join(f"{e:.5f}" for e in errs))
    ctx.check(max(errs) <= tol and all(np.isfinite(errs)),
              f"a MoE layer's output differs from the reference's sparse FFN "
              f"by {max(errs):.5f} of its norm, more than {tol}")


def check_reference(ctx, engine, cfg, conf, reference, batches) -> float:
    """``eval_batch`` against the float32 reference over the engine's own
    master weights, on one row a data-parallel rank; returns the loss."""
    tol = conf["reference_check"]
    rows = engine.dp_world
    ids = next(batches)["input_ids"][:rows]
    got = float(engine.eval_batch({"input_ids": ids, "labels": ids}))
    sparse = "num_experts" in conf
    hidden = []
    ce, aux = reference.loss_parts(
        engine.state.params, ids, **reference_kwargs(conf),
        **({"ffn_inputs": hidden} if sparse else {}))
    want = float(ce) + float(aux)
    ctx.log(f"reference check: engine loss {got:.6f}  reference {want:.6f} "
            f"(cross-entropy {float(ce):.6f} + router losses {float(aux):.6f})"
            f"  difference {got - want:+.6f}")
    ctx.check(abs(got - want) <= tol["loss_abs_tol"],
              f"eval loss {got} differs from the reference {want} by more "
              f"than {tol['loss_abs_tol']}")
    if sparse:
        check_experts(ctx, cfg, conf, reference, engine.state.params, hidden)
    return got


def _counter_total(name: str):
    from deepspeed_tpu.telemetry import get_registry

    entry = get_registry().snapshot().get(name)
    return None if not entry else sum(s["value"] for s in entry["samples"])


def run(ctx, reference) -> dict:
    import jax

    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    mix = ctx.cell.traffic
    engine, cfg, conf = build(ctx)
    flops = importlib.import_module("benchmark." + conf["flops"])
    n_dev = len(jax.devices())
    rows, seq = engine.train_batch_size, int(ctx.sized(mix)["seq_len"])
    ctx.log(f"mesh {dict(engine.mesh.shape)} global batch {rows} x {seq}")
    engine.init_params()
    scale_init(engine, conf.get("init_scale", {}))
    ctx.log("weights made on the device")
    batches = loadgen.packed_batches(ctx.sized(mix), ctx.seed, rows,
                                     cfg.vocab_size)
    check_reference(ctx, engine, cfg, conf, reference, batches)

    run_ahead = int(conf["run_ahead_steps"])
    with ctx.span("warmup"):
        warm = [engine.train_batch(data_iter=batches)
                for _ in range(int(conf["warmup_steps"]))]
        loss_before = float(jax.block_until_ready(warm[0]))
        jax.block_until_ready(warm[-1])
    ctx.start_trace()
    setup_s = time.perf_counter() - ctx.t_process
    ctx.log(f"set-up done in {setup_s:.1f}s; window {ctx.window_seconds}s")

    compiles0 = ctx.compiles
    losses, ready_t, routed = [], [], []
    seconds = ctx.window_seconds
    with ctx.span("window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with ctx.span("train_batch"):
                losses.append(engine.train_batch(data_iter=batches))
            if len(losses) > run_ahead:
                with ctx.span("wait_device"):
                    jax.block_until_ready(losses[-1 - run_ahead])
                ready_t.append(time.perf_counter())
                routed.append(moe_load_imbalance.snapshot())
        jax.block_until_ready(losses[-1])
        t1 = time.perf_counter()
    ctx.stop_trace()
    window_s = t1 - t0
    steps = len(losses)
    vals = np.asarray([float(x) for x in losses])
    compiles_in_window = ctx.compiles - compiles0
    engine.drain_step_stats(wait=True)
    routed.append(moe_load_imbalance.snapshot())

    bad = int((~np.isfinite(vals)).sum())
    ctx.check(bad == 0, f"{bad} of {steps} losses are not finite")
    ctx.check(compiles_in_window == 0,
              f"{compiles_in_window} executables were built inside the window")
    tail_n = max(1, steps // 10)
    ctx.check(vals[-tail_n:].mean() < loss_before,
              f"mean loss of the last {tail_n} steps "
              f"{vals[-tail_n:].mean():.4f} is not below the loss before "
              f"the first update {loss_before:.4f}")
    if "num_experts" in conf:
        dropped = _counter_total(DROPPED)
        ctx.check(dropped == 0, f"{DROPPED} reads {dropped}: the dropless "
                                f"dispatch dropped (token, choice) pairs")
    impls = {(s, i) for s, i, _, n in dispatch_report() if n}
    if not ctx.rehearse:
        for site in ("attention", "grouped_matmul"):
            want = conf.get(f"expect_{site}_impl")
            ctx.check(want is None or (site, want) in impls,
                      f"{site} never resolved to {want}: {sorted(impls)}")

    tokens = steps * rows * seq
    step_tokens = rows * seq // n_dev
    ctx.log(f"{steps} steps, {tokens} tokens in {window_s:.3f}s; loss "
            f"{loss_before:.3f} -> {vals[-tail_n:].mean():.3f}")
    observed = {
        "step_ready_t": ready_t, "steps": steps, "tokens": tokens,
        "n_devices": n_dev,
        "flops_per_token": flops.train_flops_per_token(conf, seq),
        "attention_flops_per_token":
            flops.causal_attention_flops_per_token(conf, seq, 3),
        "attention_bytes_per_token": flops.flash_train_bytes_per_token(conf),
        moe_load_imbalance.COUNTER: routed,
        "instruction_scopes": ctx.step_scopes(engine),
    }
    if "num_experts" in conf:
        observed["expert_gemm_flops_per_step"] = \
            flops.expert_gemm_flops_per_step(conf, step_tokens)
        observed["expert_gemm_bytes_per_step"] = \
            flops.expert_gemm_bytes_per_step(conf, step_tokens)
    return {
        "setup_s": setup_s, "window_s": window_s,
        "attempted": steps, "failed": bad,
        "compiles_in_window": compiles_in_window,
        "counts": {"steps": steps, "tokens": tokens},
        "end_to_end": {
            "train_tokens_per_s_chip": tokens / window_s / n_dev},
        "observed": observed,
    }
