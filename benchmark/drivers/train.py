"""Drive the trainer: ``deepspeed_tpu.initialize`` → ``init_params`` → a
data iterator of packed documents → ``train_batch``, for ``--seconds``.

Set-up: build the engine, make the weights on the device from the seed,
compare ``eval_batch`` with the plain reference on seeded rows, take the
warm-up steps.  Window: ``train_batch(data_iter=...)`` back to back, the
host allowed ``run_ahead`` steps in front of the device, fenced by
``block_until_ready`` on the last loss.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import flops, loadgen


def build(ctx):
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    conf = ctx.sized(ctx.cell.config)
    cfg = GPT2Config(vocab_size=conf["vocab_size"],
                     n_positions=conf["n_positions"], n_embd=conf["n_embd"],
                     n_layer=conf["n_layer"], n_head=conf["n_head"],
                     layer_norm_epsilon=conf["layer_norm_epsilon"],
                     **conf["model_options"])
    ds = dict(conf["engine"])
    ds["train_micro_batch_size_per_gpu"] = conf["micro_per_device"]
    ds["seed"] = ctx.seed % (2**31 - 1)
    ds["steps_per_print"] = 10**9
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg), config=ds)
    return engine, cfg, conf


def check_reference(ctx, engine, cfg, reference, batches) -> float:
    """``eval_batch`` against the float32 reference over the engine's own
    master weights, on one row a data-parallel rank; returns the loss."""
    tol = ctx.sized(ctx.cell.config)["reference_check"]
    rows = engine.dp_world
    ids = next(batches)["input_ids"][:rows]
    got = float(engine.eval_batch({"input_ids": ids, "labels": ids}))
    want = float(reference.next_token_loss(
        engine.state.params, ids, n_layer=cfg.n_layer, n_head=cfg.n_head,
        vocab_size=cfg.vocab_size, eps=cfg.layer_norm_epsilon))
    ctx.log(f"reference check: engine loss {got:.6f}  reference {want:.6f}")
    ctx.check(abs(got - want) <= tol["loss_abs_tol"],
              f"eval loss {got} differs from the reference {want} by more "
              f"than {tol['loss_abs_tol']}")
    return got


def run(ctx, reference) -> dict:
    import jax

    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    mix = ctx.cell.traffic
    engine, cfg, conf = build(ctx)
    n_dev = len(jax.devices())
    rows, seq = engine.train_batch_size, int(ctx.sized(mix)["seq_len"])
    ctx.log(f"mesh {dict(engine.mesh.shape)} global batch {rows} x {seq}")
    engine.init_params()
    ctx.log("weights made on the device")
    batches = loadgen.packed_batches(ctx.sized(mix), ctx.seed, rows,
                                     cfg.vocab_size)
    check_reference(ctx, engine, cfg, reference, batches)

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
    losses, ready_t = [], []
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
        jax.block_until_ready(losses[-1])
        t1 = time.perf_counter()
    ctx.stop_trace()
    window_s = t1 - t0
    steps = len(losses)
    vals = np.asarray([float(x) for x in losses])
    compiles_in_window = ctx.compiles - compiles0

    bad = int((~np.isfinite(vals)).sum())
    ctx.check(bad == 0, f"{bad} of {steps} losses are not finite")
    ctx.check(compiles_in_window == 0,
              f"{compiles_in_window} executables were built inside the window")
    tail_n = max(1, steps // 10)
    ctx.check(vals[-tail_n:].mean() < loss_before,
              f"mean loss of the last {tail_n} steps "
              f"{vals[-tail_n:].mean():.4f} is not below the loss before "
              f"the first update {loss_before:.4f}")
    impls = {(s, i) for s, i, _, n in dispatch_report() if n}
    if not ctx.rehearse:
        ctx.check(("attention", conf["expect_attention_impl"]) in impls,
                  f"attention never resolved to "
                  f"{conf['expect_attention_impl']}: {sorted(impls)}")

    tokens = steps * rows * seq
    per_token = flops.train_flops_per_token(cfg.n_embd, cfg.n_layer,
                                            cfg.vocab_size, seq)
    ctx.log(f"{steps} steps, {tokens} tokens in {window_s:.3f}s; loss "
            f"{loss_before:.3f} -> {vals[-tail_n:].mean():.3f}")
    return {
        "setup_s": setup_s, "window_s": window_s,
        "attempted": steps, "failed": bad,
        "compiles_in_window": compiles_in_window,
        "counts": {"steps": steps, "tokens": tokens},
        "end_to_end": {
            "train_tokens_per_s_chip": tokens / window_s / n_dev},
        "observed": {
            "step_ready_t": ready_t, "steps": steps, "tokens": tokens,
            "n_devices": n_dev, "flops_per_token": per_token,
            "attention_flops_per_token":
                flops.causal_attention_flops_per_token(
                    cfg.n_embd, cfg.n_layer, seq, 3),
            "attention_bytes_per_token":
                flops.flash_train_bytes_per_token(cfg.n_embd, cfg.n_layer),
            "instruction_scopes": ctx.step_scopes(engine),
        },
    }
