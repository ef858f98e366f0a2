#!/usr/bin/env python3
"""Device time of a cell's step by the program's own scopes: the cell's
trainer as the benchmark builds it, three warm steps, then
``engine.profile_device_scopes`` (``deepspeed_tpu/telemetry/
device_scopes.py``), which traces a few steps and names every device
instruction through the optimized HLO of the executable that ran.  Nothing
is lowered or compiled for the reading.

    chiprun -- python3 scripts/probe_mellum2_scopes.py [--steps 6]
        [--cell train-trinity-mini-8k-1chip] [--depth 3] [--top moe/route]

One JSON line each: the step's booked memory, the seconds reading it took
and whether its executable was built or fetched; device ms a step, the
seconds ``as_text()`` and its parse took and the executables the two
readings made (0); (scope, pass) rows; what has no
``op_name`` by kind; each ``--top`` scope by op and its ten heaviest
instructions.
"""
import argparse
import json
import time

from mellum2_cell import build


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=3000000029)
    ap.add_argument("--cell", default="train-mellum2-8k-1chip")
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--top", action="append", default=[])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU sizes: runs up to the trace, which needs the chip")
    args = ap.parse_args()

    import jax

    from deepspeed_tpu.telemetry import device_scopes, get_registry

    count_row_dma_starts()
    _, _, engine, _, _, batches = build(args.seed, rehearse=args.rehearse,
                                        cell=args.cell)
    for _ in range(3):
        jax.block_until_ready(engine.train_batch(data_iter=batches))
    def executables(span=None):
        return {how: sum(s["value"] for s in get_registry().snapshot()
                         ["xla_executables_total"]["samples"]
                         if s["labels"]["how"] == how
                         and span in (None, s["labels"]["span"]))
                for how in ("built", "fetched")}

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        return out, round(time.perf_counter() - t0, 3)

    before = executables()
    memory, memory_s = timed(engine.record_memory_profile)
    print(json.dumps({"cell": args.cell, "memory": memory,
                      "memory_analysis_s": memory_s,
                      "step_executables": executables("train/dispatch")}))
    # the one parse of the step's text, which the table would make itself
    _, parse_s = timed(lambda: device_scopes.instruction_scopes(
        engine.compiled_step()))
    table = engine.profile_device_scopes(batches, steps=args.steps,
                                         depth=args.depth, top=args.top)
    after = executables()
    print(json.dumps({
        "steps": table["steps"], "device_ms_a_step": table["device_ms_a_step"],
        "as_text_and_parse_s": parse_s,
        "no_op_name_ms_a_step": sum(r["ms_a_step"]
                                    for r in table["no_op_name"]),
        "executables_made_by_the_two_readings":
            sum(after.values()) - sum(before.values())}))
    for row in table["scopes"]:
        print(json.dumps(row))
    for row in table["no_op_name"][:12]:
        print(json.dumps({"no_op_name": row["kind"],
                          "ms_a_step": row["ms_a_step"]}))
    for scope, split in table["top"].items():
        for row in split["ops"] + split["instructions"]:
            print(json.dumps({"top": scope, **row}))
    # what this process's traces resolved each per-head norm site to (init,
    # the eval step and the train step: a layer is traced more than once)
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    for site, impl, reason, count in dispatch_report():
        if site in ("qk_rows", "gated_norm_rows"):
            print(json.dumps({"site": site, "impl": impl, "reason": reason,
                              "count": count}))
    print(json.dumps(row_kernel_counters(engine)))


def count_row_dma_starts():
    """Stand in front of ``parallel/moe.py record_stats`` (the model looks
    it up at each finished step) and book ``moe_rows_dma_starts_total
    {kernel="gather", where=block|loop}`` from the step's counts, on the
    host: a layer's pairs held here are its gather's live rows, and where
    the kernel starts them is a function of those alone
    (``ops/pallas/moe_rows.py gather_starts``).  One call a layer a step
    (a training step makes three on the same routing); the program itself
    books nothing."""
    import numpy as np

    from deepspeed_tpu.ops.pallas import moe_rows
    from deepspeed_tpu.parallel import moe
    from deepspeed_tpu.telemetry import get_registry

    booked = moe.record_stats

    def record_stats(stats):
        booked(stats)
        if "elsewhere" not in stats:    # every expert held: XLA's gather
            return
        counts = np.asarray(stats["tokens_per_expert"])
        rows = counts.reshape(-1, counts.shape[-1]).sum(axis=1)
        live = rows - np.asarray(stats["elsewhere"]).reshape(-1)
        starts = get_registry().counter(
            "moe_rows_dma_starts_total", "row DMAs of one gather call a "
            "layer a step, by where the kernel starts them: inside a "
            "vector block or in a loop of their own", ("kernel", "where"))
        for n, r in zip(live, rows):
            for where, v in zip(("block", "loop"),
                                moe_rows.gather_starts(int(n), int(r))):
                starts.labels("gather", where).inc(float(v))

    moe.record_stats = record_stats


def row_kernel_counters(engine):
    """What the registry holds of the MoE row kernels once every step's
    statistics are booked: ``moe_rows_dma_starts_total`` (after
    :func:`count_row_dma_starts`, and only where the kernels moved the
    rows) with the gather's share started inside a vector block, the
    kernel bodies traced, and the seconds of tracing and lowering so far
    (every span)."""
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report
    from deepspeed_tpu.telemetry import get_registry

    engine.drain_step_stats(wait=True)
    snap = get_registry().snapshot()

    def samples(name):
        return (snap.get(name) or {"samples": ()})["samples"]

    out = {}
    if any(site == "moe_rows" and impl == "pallas" and n
           for site, impl, _, n in dispatch_report()):
        starts = {s["labels"]["where"]: s["value"]
                  for s in samples("moe_rows_dma_starts_total")}
        out["moe_rows_dma_starts_total"] = starts
        if sum(starts.values()):
            out["gather_block_share"] = round(
                starts["block"] / sum(starts.values()), 4)
    out["moe_rows_traces_total"] = sum(
        s["value"] for s in samples("moe_rows_traces_total"))
    out["trace_lower_s"] = round(sum(
        s["value"] for s in samples("xla_compile_seconds_total")
        if s["labels"]["phase"] in ("trace", "lower")), 3)
    return out


if __name__ == "__main__":
    main()
