#!/usr/bin/env python3
"""Device time of ``train-mellum2-8k-1chip``'s step by the program's own
scopes.  The v5e's trace names a device event by its HLO instruction and
carries no ``op_name``, and the benchmark's reduction adds instructions up
by kind (``fusion`` is half of this step): this probe traces a few steps,
keeps every instruction's own name, and maps it to the ``op_name`` of the
same executable's optimized HLO (``jit(step_fn)/.../layers_1/moe/combine/
...``), then adds up by scope and by pass (forward, the backward's
recomputation, backward).

    chiprun -- python3 scripts/probe_mellum2_scopes.py [--steps 6]
        [--cell train-trinity-mini-8k-1chip]

The first line also gives the ``copy`` instructions' own total, the
ledger's ``breakdown`` ``copy``.
"""
import argparse
import collections
import json
import os
import re
import shutil

from mellum2_cell import ROOT, build

SCOPES = ("moe/combine", "moe/dispatch", "moe/route", "moe/experts",
          "self_attn_window", "self_attn_full", "rope", "attn/qk_norm",
          "attn/gate", "loss_head",
          "self_attn", "post_attention_norm", "input_norm", "moe", "norm",
          "embed")


def scope_of(op_name: str) -> str:
    if "/layers_" not in op_name and "loss_head" not in op_name \
            and "LlamaForCausalLM" not in op_name:
        return "optimizer and the rest of the step"
    return next((s for s in SCOPES if s in op_name), "model, other")


def pass_of(op_name: str) -> str:
    if "transpose(" not in op_name:
        return "forward"
    return "recompute" if "checkpoint" in op_name.split("transpose(", 1)[1] \
        and "/jvp(" in op_name.split("transpose(", 1)[1] else "backward"


def measure(engine, batches, steps: int) -> list:
    """Trace ``steps`` steps of a warm ``engine``; the lines to print:
    device ms a step by scope and pass, by instruction kind for what has
    no ``op_name``, and the ``copy`` instructions' own total."""
    import jax
    import numpy as np

    from benchmark import trace_reduce

    batch = next(batches)
    a_batch = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), batch)
    text = engine._compiled_train_step.lower(
        engine.abstract_state(batch), a_batch).compile().as_text()
    op_names = dict(re.findall(
        r"%?([\w.\-]+) = [^\n]*?metadata=\{op_name=\"([^\"]*)\"", text))

    out = os.path.join(ROOT, ".bench_out", "probe_scopes")
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out)
    for _ in range(steps):
        loss = engine.train_batch(data_iter=batches)
    jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    named = trace_reduce._op_name
    trace_reduce._op_name = lambda e: e.name.split(" = ")[0].lstrip("%")
    try:
        dev_ops, _, _ = trace_reduce.read_xplane(trace_reduce.find_xplane(out))
    finally:
        trace_reduce._op_name = named
    events = next(iter(dev_ops.values()))
    by = collections.Counter()
    unnamed = collections.Counter()
    kinds = collections.Counter()
    for name, _, dur in trace_reduce.self_times(events):
        kinds[re.sub(r"[.\-_]\d+$", "", name)] += dur
        op = op_names.get(name)
        if op is None:
            unnamed[re.sub(r"[.\-_]\d+$", "", name)] += dur
            continue
        by[scope_of(op), pass_of(op)] += dur
    ms = lambda ns: round(ns / steps / 1e6, 2)
    total = sum(by.values()) + sum(unnamed.values())
    lines = [{"steps": steps, "device_ms_a_step": ms(total),
              "copy_ms_a_step": ms(kinds["copy"])}]
    lines += [{"scope": scope, "pass": pass_, "ms_a_step": ms(ns)}
              for (scope, pass_), ns in sorted(by.items(),
                                               key=lambda kv: -kv[1])]
    lines += [{"no_op_name": name, "ms_a_step": ms(ns)}
              for name, ns in unnamed.most_common(8)]
    shutil.rmtree(out, ignore_errors=True)
    return lines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=3000000029)
    ap.add_argument("--cell", default="train-mellum2-8k-1chip")
    args = ap.parse_args()

    import jax

    _, _, engine, _, _, batches = build(args.seed, cell=args.cell)
    for _ in range(3):
        jax.block_until_ready(engine.train_batch(data_iter=batches))
    for line in measure(engine, batches, args.steps):
        print(json.dumps(line))
    # what this process's traces resolved each q / k site to (init, the
    # eval step and the train step: a layer is traced more than once)
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    for site, impl, reason, count in dispatch_report():
        if site == "qk_rows":
            print(json.dumps({"site": site, "impl": impl, "reason": reason,
                              "count": count}))


if __name__ == "__main__":
    main()
