#!/usr/bin/env python3
"""Routing of ``train-qwen3next-gdn-8k-1chip`` by step: a layer's max /
mean pairs an expert, the share of its pairs on the 32 held experts (even:
6.25%), how many held experts saw no pair, and the loss, every ``--every``
steps from random weights, under a router-loss weight (``--aux``) and an
embedding scale (``--init-scale``).  Top-10 of 512 softmax-routed experts:
what the recipe (weight, scale, warm-up steps) has to bring to rest before
the window.  ``scripts/probe_sdar_routing.py`` for this cell.

    chiprun -- python3 scripts/probe_qwen3next_routing.py [--aux 0.1]
        [--init-scale 50] [--warmup-lr 2000] [--steps 120] [--every 10]
"""
import argparse
import json
import time

from mellum2_cell import build

CELL = "train-qwen3next-gdn-8k-1chip"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--init-scale", type=float, default=None)
    ap.add_argument("--aux", type=float, default=None)
    ap.add_argument("--warmup-lr", type=int, default=None,
                    help="steps of a linear learning-rate warm-up from 0 "
                         "(engine.scheduler WarmupLR); 0: a constant rate")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=3000000023)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax

    from benchmark.layer_metrics import moe_load_imbalance

    def edit(conf):
        if args.aux is not None:
            conf["moe"] = dict(conf["moe"], aux_loss_weight=args.aux)
        if args.warmup_lr is not None:
            engine = {k: v for k, v in conf["engine"].items()
                      if k != "scheduler"}
            if args.warmup_lr:
                engine["scheduler"] = {"type": "WarmupLR", "params": {
                    "warmup_min_lr": 0.0,
                    "warmup_max_lr": engine["optimizer"]["params"]["lr"],
                    "warmup_num_steps": args.warmup_lr,
                    "warmup_type": "linear"}}
            conf["engine"] = engine

    _, _, engine, cfg, conf, batches = build(args.seed, args.rehearse, edit,
                                             args.init_scale, cell=CELL)
    first, held = cfg.moe.first_expert, cfg.moe.num_experts
    print(json.dumps({"aux_loss_weight": conf["moe"]["aux_loss_weight"],
                      "scheduler": conf["engine"].get("scheduler"),
                      "init_scale": conf.get("init_scale")
                      if args.init_scale is None else args.init_scale,
                      "seed": args.seed}), flush=True)
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
        here = [r[first:first + held] for r in routed]
        print(json.dumps({
            "step": step + 1, "loss": round(loss, 4),
            "s_a_step": round((time.perf_counter() - t0)
                              / (1 if step == 0 else args.every), 4),
            "max_over_mean": [round(float(r.max() / r.mean()), 2)
                              for r in routed],
            "held_pct": [round(float(100 * h.sum() / r.sum()), 2)
                         for h, r in zip(here, routed)],
            "held_idle": [int((h == 0).sum()) for h in here]}), flush=True)
        t0 = time.perf_counter()


if __name__ == "__main__":
    main()
