#!/usr/bin/env python
"""One 125M-headline config measurement per invocation (mirrors
bench_train's config).  Usage:
  python scripts/sweep_125m.py micro=24 fb=1024x1024
Prints one JSON line.
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
    micro = int(kv.get("micro", 24))
    chunk = int(kv.get("chunk", 1 << 30))
    remat = kv.get("remat", "off")
    fb = kv.get("fb")
    steps = int(kv.get("steps", 8))
    clip = float(kv.get("clip", 1.0))

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    preset = "gpt2-125m" if on_tpu else "gpt2-tiny"
    seq = SEQ if on_tpu else 128

    vocab = int(kv.get("vocab", 0))   # shrink the head to isolate its cost
    cfg = gpt2_config(
        preset, n_positions=seq, scan_layers=not on_tpu,
        remat=remat != "off",
        remat_policy=remat if remat != "off" else "nothing_saveable",
        attn_impl=kv.get("attn", "auto"),
        flash_block=tuple(int(x) for x in fb.split("x")) if fb else None,
        loss_chunk=chunk or None,
        **({"vocab_size": vocab} if vocab else {}))
    model = GPT2LMHeadModel(cfg)
    gas = int(kv.get("gas", 1))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": kv.get("opt", "adamw"),
                      "params": {"lr": 1e-4, "weight_decay": 0.1}},
        "gradient_clipping": clip,
        "zero_optimization": {"stage": 1},
        "data_types": {"grad_accum_dtype": kv.get("accum", "fp32")},
        "steps_per_print": 10**6,
    })
    engine.init_params()
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size,
        size=(engine.train_batch_size, seq)).astype(np.int32)
    batch = engine.prepare_batch({"input_ids": ids, "labels": ids})
    losses = engine.train_batches(batch, steps=steps, stacked=False)
    jax.device_get(losses)
    windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        losses = engine.train_batches(batch, steps=steps, stacked=False)
        jax.device_get(losses)
        windows.append(engine.train_batch_size * seq * steps
                       / (time.perf_counter() - t0))
    import statistics

    tok_s = statistics.median(windows)
    mfu = tok_s * model.flops_per_token() / (PEAK if on_tpu else 1e12)
    print(json.dumps({
        "config": {"micro": micro, "gas": gas, "chunk": chunk,
                   "remat": remat, "fb": fb, "clip": clip},
        "tok_s": round(tok_s, 1), "mfu": round(mfu, 4),
        "vs_ref": round(mfu / REF_MFU, 3),
        "windows": [round(w, 1) for w in windows],
        "final_loss": float(jax.device_get(losses)[-1]),
    }), flush=True)


if __name__ == "__main__":
    main()
