#!/usr/bin/env python3
"""How the routing of ``train-mellum2-8k-1chip`` moves over its first
steps: a layer's load imbalance (max / mean pairs an expert, over all 64)
and the share of the pairs routed to the 16 experts held here, every
``--every`` steps, for one variant of what the configuration file assumes
(``--init-scale`` on the embedding table, ``--aux`` the load-balancing
weight, ``--first`` the first held expert).  Reads the program's own
counter, as the benchmark's readers do.

    chiprun -- python3 scripts/probe_mellum2_routing.py --init-scale 50 --aux 0.001
"""
import argparse
import json

from mellum2_cell import build


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--init-scale", type=float, default=50.0)
    ap.add_argument("--aux", type=float, default=None,
                    help="load-balancing weight (default: the file's)")
    ap.add_argument("--first", type=int, default=None,
                    help="first held expert (default: the file's)")
    ap.add_argument("--warmup", type=int, default=None,
                    help="linear learning-rate warm-up steps to the file's "
                         "lr (default: the file's schedule; 0: constant)")
    ap.add_argument("--steps", type=int, default=70)
    ap.add_argument("--every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=3000000023)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark.layer_metrics import moe_load_imbalance

    def edit(conf):
        conf["moe"] = dict(conf["moe"])
        if args.aux is not None:
            conf["moe"]["aux_loss_weight"] = args.aux
        if args.first is not None:
            conf["moe"]["first_expert"] = args.first
        if args.warmup is not None:
            conf["engine"] = dict(conf["engine"])
            conf["engine"].pop("scheduler", None)
            if args.warmup:
                lr = conf["engine"]["optimizer"]["params"]["lr"]
                conf["engine"]["scheduler"] = {
                    "type": "WarmupLR", "params": {
                        "warmup_min_lr": 0.0, "warmup_max_lr": lr,
                        "warmup_num_steps": args.warmup,
                        "warmup_type": "linear"}}

    _, _, engine, cfg, conf, batches = build(args.seed, args.rehearse, edit,
                                             args.init_scale)
    args.aux = conf["moe"]["aux_loss_weight"]
    first, held = cfg.moe.first_expert, cfg.moe.num_experts
    before = None
    for step in range(args.steps):
        loss = float(engine.train_batch(data_iter=batches))
        engine.drain_step_stats(wait=True)
        snap = moe_load_imbalance.snapshot()
        if step % args.every == 0 or step == args.steps - 1:
            d = snap if before is None else snap - before
            print(json.dumps({
                "init_scale": args.init_scale, "aux": args.aux,
                "warmup": args.warmup, "step": step,
                "loss": round(loss, 3),
                "max_over_mean": [round(float(r.max() / r.mean()), 2)
                                  for r in d],
                "held_pct": [round(100 * float(r[first:first + held].sum()
                                               / r.sum()), 1) for r in d],
            }), flush=True)
        before = snap


if __name__ == "__main__":
    main()
