#!/usr/bin/env python3
"""Routing of ``train-sdar-blockdiff-8k-1chip`` by step: a layer's max /
mean pairs an expert and the share of its pairs on the 16 held experts,
the loss and the masked share, every ``--every`` steps from random
weights, under a router-loss weight (``--aux``) and an embedding scale
(``--init-scale``).  Block diffusion gives every [MASK] position the same
embedding, so at layer 0 about a quarter of all 2L rows reach the router
alike: what the recipe (weight, warm-up steps) has to bring to rest before
the window.  ``scripts/probe_mellum2_routing.py`` for this cell, with the
data drawn as the cell's driver draws it (the slice less the mask id).

    chiprun -- python3 scripts/probe_sdar_routing.py [--aux 0.1]
        [--steps 150] [--every 10]
"""
import argparse
import json
import time

from mellum2_cell import build

CELL = "train-sdar-blockdiff-8k-1chip"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--init-scale", type=float, default=None)
    ap.add_argument("--aux", type=float, default=None)
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=3000000023)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark import loadgen
    from benchmark.layer_metrics import (diffusion_masked_pct,
                                         moe_load_imbalance)

    def edit(conf):
        if args.aux is not None:
            conf["moe"] = dict(conf["moe"], aux_loss_weight=args.aux)

    cell, _, engine, cfg, conf, _ = build(args.seed, args.rehearse, edit,
                                          args.init_scale, cell=CELL)
    mix = {k: v for k, v in cell.traffic.items() if k != "rehearse"}
    if args.rehearse:
        mix.update(cell.traffic["rehearse"])
    batches = loadgen.packed_batches(mix, args.seed, engine.train_batch_size,
                                     cfg.vocab_size - 1)
    first, held = cfg.moe.first_expert, cfg.moe.num_experts
    print(json.dumps({"aux_loss_weight": conf["moe"]["aux_loss_weight"],
                      "init_scale": conf.get("init_scale"),
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
        print(json.dumps({
            "step": step + 1, "loss": round(loss, 4),
            "s_a_step": round((time.perf_counter() - t0)
                              / (1 if step == 0 else args.every), 4),
            "max_over_mean": [round(float(r.max() / r.mean()), 2)
                              for r in routed],
            "held_pct": [round(float(100 * r[first:first + held].sum()
                                     / r.sum()), 2) for r in routed],
            "masked_pct": round(100 * diffusion_masked_pct.share(
                diffusion_masked_pct.totals()), 2)}), flush=True)
        t0 = time.perf_counter()


if __name__ == "__main__":
    main()
