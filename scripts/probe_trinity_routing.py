#!/usr/bin/env python3
"""How the routing of ``train-trinity-mini-8k-1chip`` moves over its first
steps under the selection bias alone: every ``--every`` steps, over the
steps since the last line, a layer's load imbalance (max / mean pairs an
expert, over all 128), the share of the pairs routed to the 16 experts
held here and the experts that received none; the bias's max - min, the
loss and the seconds since; for one variant of what the configuration file
assumes (``--init-scale`` on the embedding table, ``--rate`` of the bias
update).  ``--check`` first runs the cell's own comparison against the
reference and prints its lines.  Reads the program's own counters, as the
benchmark's readers do.

    chiprun -- python3 scripts/probe_trinity_routing.py --steps 200 --check
"""
import argparse
import json
import time

from mellum2_cell import build

CELL = "train-trinity-mini-8k-1chip"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--init-scale", type=float, default=None)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--micro", type=int, default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=3000000023)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--cell", default=CELL,
                    help="another cell routed under a selection bias "
                         "(train-joyai-flash-8k-1chip)")
    args = ap.parse_args()

    import numpy as np

    from benchmark.harness.runner import Context
    from benchmark.layer_metrics import moe_load_imbalance

    def edit(conf):
        if args.rate is not None:
            conf["moe"] = dict(conf["moe"], bias_update_rate=args.rate)
        if args.micro is not None:
            conf["micro_per_device"] = args.micro

    t0 = time.perf_counter()
    cell, driver, engine, cfg, conf, batches = build(
        args.seed, args.rehearse, edit, args.init_scale, cell=args.cell)
    print(json.dumps({"built_s": time.perf_counter() - t0}), flush=True)
    if args.check:
        ctx = Context(cell, args.seed, 0.0, False, args.rehearse, None, t0)
        driver.check_reference(ctx, engine, cfg, conf, cell.reference(),
                               batches)
        print(json.dumps({"check_s": time.perf_counter() - t0,
                          "correct": not ctx.notes, "notes": ctx.notes}),
              flush=True)
    first, held = cfg.moe.first_expert, cfg.moe.num_experts
    before, t_last = None, time.perf_counter()
    for step in range(args.steps):
        loss = float(engine.train_batch(data_iter=batches))
        engine.drain_step_stats(wait=True)
        snap = moe_load_imbalance.snapshot()
        if step % args.every == 0 or step == args.steps - 1:
            d = snap if before is None else snap - before
            before = snap
            now = time.perf_counter()
            bias = [np.asarray(engine.state.params[f"layers_{i}"]["moe"]
                               ["gate"]["expert_bias"])
                    for i in range(cfg.num_dense_layers,
                                   cfg.num_hidden_layers)]
            print(json.dumps({
                "step": step, "loss": round(loss, 4),
                "s_since": round(now - t_last, 2),
                "imbalance": [round(float(r.max() / r.mean()), 2) for r in d],
                "held_pct": [round(100 * float(r[first:first + held].sum()
                                               / r.sum()), 2) for r in d],
                "bias_spread": [round(float(np.ptp(b)), 4) for b in bias],
                "idle_experts": [int((r == 0).sum()) for r in d]}),
                flush=True)
            t_last = now
    print(json.dumps({"total_s": time.perf_counter() - t0,
                      "peak_bytes": max(int((d.memory_stats() or {}).get(
                          "peak_bytes_in_use", 0))
                          for d in __import__("jax").devices())}), flush=True)


if __name__ == "__main__":
    main()
