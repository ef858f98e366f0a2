#!/usr/bin/env python3
"""What the routers of ``train-lfm2-hybrid-8k-1chip`` need from random
weights: every ``--every`` steps, over the steps since the last line, each
expert layer's max / mean pairs an expert (over all 64), the share of its
pairs on the 16 experts held here and the experts that received none; the
bias's max - min, the loss and the seconds a step; for one variant of what
the configuration file assumes: ``--rate`` of the bias update, ``--warmup``
steps of a linear learning-rate warm-up (the family's kind, as JoyAI's
cell runs), or nothing.  The table is tied, so there is no ``--init-scale``:
a scaled table would scale the logits too.  ``--scopes`` ends with the
device's time by the program's scopes (``engine.profile_device_scopes``,
the ``short_conv/`` and attention scopes four deep and the ten heaviest
instructions under ``short_conv``).

    chiprun -- python3 scripts/probe_lfm2_routing.py --steps 120 [--scopes]
"""
import argparse
import json
import time

from mellum2_cell import build

CELL = "train-lfm2-hybrid-8k-1chip"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--warmup", type=int, default=None)
    ap.add_argument("--micro", type=int, default=None)
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=3000000023)
    ap.add_argument("--scopes", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.layer_metrics import moe_load_imbalance
    from probe_mellum2_scopes import count_row_dma_starts, row_kernel_counters

    count_row_dma_starts()

    def edit(conf):
        if args.rate is not None:
            conf["moe"] = dict(conf["moe"], bias_update_rate=args.rate)
        if args.micro is not None:
            conf["micro_per_device"] = args.micro
        if args.warmup is not None:
            lr = conf["engine"]["optimizer"]["params"]["lr"]
            conf["engine"] = dict(conf["engine"], scheduler={
                "type": "WarmupLR", "params": {
                    "warmup_min_lr": 0.0, "warmup_max_lr": lr,
                    "warmup_num_steps": args.warmup,
                    "warmup_type": "linear"}})

    cell, _, engine, cfg, conf, batches = build(args.seed, args.rehearse,
                                                edit, cell=CELL)
    first, held = cfg.moe.first_expert, cfg.moe.num_experts
    print(json.dumps({"bias_update_rate": conf["moe"]["bias_update_rate"],
                      "scheduler": conf["engine"].get("scheduler"),
                      "rows": conf["micro_per_device"], "seed": args.seed}),
          flush=True)
    before, t0 = None, time.perf_counter()
    for step in range(args.steps):
        loss = engine.train_batch(data_iter=batches)
        if (step + 1) % args.every and step:
            continue
        loss = float(jax.block_until_ready(loss))
        engine.drain_step_stats(wait=True)
        now = moe_load_imbalance.snapshot()
        routed = now if before is None else now - before
        before = now
        bias = [np.asarray(engine.state.params[f"layers_{i}"]["moe"]["gate"]
                           ["expert_bias"])
                for i in range(cfg.num_dense_layers, cfg.num_hidden_layers)]
        print(json.dumps({
            "step": step + 1, "loss": round(loss, 4),
            "s_a_step": round((time.perf_counter() - t0)
                              / (1 if step == 0 else args.every), 4),
            "max_over_mean": [round(float(r.max() / r.mean()), 2)
                              for r in routed],
            "held_pct": [round(float(100 * r[first:first + held].sum()
                                     / r.sum()), 2) for r in routed],
            "idle_experts": [int((r == 0).sum()) for r in routed],
            "bias_spread": [round(float(np.ptp(b)), 4) for b in bias]}),
            flush=True)
        t0 = time.perf_counter()
    if args.scopes and not args.rehearse:
        table = engine.profile_device_scopes(batches, steps=4, depth=4,
                                             top=("short_conv",))
        print(json.dumps({"device_ms_a_step": table["device_ms_a_step"]}))
        for row in table["scopes"][:40]:
            print(json.dumps(row))
        print(json.dumps(table["top"]))
    print(json.dumps(row_kernel_counters(engine)))
    print(json.dumps({"peak_bytes": max(int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in jax.devices())}), flush=True)


if __name__ == "__main__":
    main()
