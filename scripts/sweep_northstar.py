#!/usr/bin/env python
"""One north-star (GPT-2-1.5B) config measurement per invocation.

Usage: python scripts/sweep_northstar.py micro=4 gas=1 chunk=8192 \
           remat=dots_saveable steps=8
Prints one JSON line; run sequentially from a shell loop for a sweep
(fresh process per config keeps HBM fragmentation out of the numbers).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

SEQ = 1024
REF_MFU = 64.0 / 125.0
PEAK = 197e12


def main():
    kv = dict(a.split("=", 1) for a in sys.argv[1:])
    micro = int(kv.get("micro", 2))
    gas = int(kv.get("gas", 1))
    chunk = int(kv.get("chunk", 0))          # 0 = dense head
    remat = kv.get("remat", "dots_saveable")  # "off" disables
    steps = int(kv.get("steps", 8))
    opt = kv.get("opt", "adamw8bit")
    accum = kv.get("accum", "bf16" if gas > 1 else "fp32")

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    preset = "gpt2-1.5b" if on_tpu else "gpt2-tiny"
    seq = SEQ if on_tpu else 128

    fb = kv.get("fb")                        # e.g. fb=256x512
    cfg = gpt2_config(
        preset, n_positions=seq, scan_layers=not on_tpu,
        remat=remat != "off",
        remat_policy=remat if remat != "off" else "nothing_saveable",
        attn_impl=kv.get("attn", "auto"),
        flash_block=tuple(int(x) for x in fb.split("x")) if fb else None,
        loss_chunk=chunk or None)
    model = GPT2LMHeadModel(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": opt,
                      "params": {"lr": 1e-4, "weight_decay": 0.1}},
        "zero_optimization": {"stage": 3},
        "data_types": {"grad_accum_dtype": accum},
        "steps_per_print": 10**6,
    })
    t_init = time.perf_counter()
    engine.init_params()
    init_s = time.perf_counter() - t_init
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size,
        size=(engine.train_batch_size, seq)).astype(np.int32)
    batch = engine.prepare_batch({"input_ids": ids, "labels": ids})
    t_c = time.perf_counter()
    losses = engine.train_batches(batch, steps=steps, stacked=False)
    jax.device_get(losses)
    compile_s = time.perf_counter() - t_c
    t0 = time.perf_counter()
    losses = engine.train_batches(batch, steps=steps, stacked=False)
    jax.device_get(losses)
    dt = time.perf_counter() - t0
    tok_s = engine.train_batch_size * seq * steps / dt
    mfu = tok_s * model.flops_per_token() / (PEAK if on_tpu else 1e12)
    print(json.dumps({
        "config": {"micro": micro, "gas": gas, "chunk": chunk,
                   "remat": remat, "opt": opt, "steps": steps},
        "tok_s": round(tok_s, 1), "mfu": round(mfu, 4),
        "vs_ref": round(mfu / REF_MFU, 3),
        "step_ms": round(1000 * dt / steps, 1),
        "init_s": round(init_s, 1), "compile_s": round(compile_s, 1),
        "final_loss": float(jax.device_get(losses)[-1]),
    }), flush=True)


if __name__ == "__main__":
    main()
