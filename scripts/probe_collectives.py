#!/usr/bin/env python3
"""A sharded cell's collectives, from inside and from the trace: the cell's
trainer as the benchmark builds it, three warm steps, the ledger of its
step's executable (``deepspeed_tpu/telemetry/device_scopes.py``
``collective_ledger``) by op, scope and pass, then
``engine.profile_device_scopes`` for the device time of those instructions
and a trace of its own for the check that the ledger and the trace agree:

- every collective kind on the ``XLA Ops`` line (names matching the
  configuration's ``trace_names.collective``, trailing number cut as
  ``benchmark/trace_reduce.py`` cuts it) is a kind the ledger holds, and
  the other way round;
- the time of the ledger's instructions that the pattern can name equals
  the time of the trace's events the pattern matches (within 2%); what is
  left is the ledger's instructions the trace names like any other fusion.

The four-chip cell waits in ``benchmark/pending_cells/``, so the probe runs
from a staged directory (``benchmark/harness/pending.py``):

    python3 -m benchmark.harness.pending train-xl-adamw-z3-4chip .pending
    python3 .pending/scripts/probe_collectives.py [--steps 4]

(both in one ``chiprun --chips 4 -- sh -c '... && ...'``).

One JSON line each; all of it also in ``chiprun_out/probe_collectives.json``.
"""
import argparse
import collections
import json
import os
import re
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=3000000047)
    ap.add_argument("--cell", default="train-xl-adamw-z3-4chip")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU sizes: the ledger, and no trace (the chip's)")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from mellum2_cell import build

    import jax

    from deepspeed_tpu.telemetry import device_scopes, get_registry

    out = {"cell": args.cell, "devices": len(jax.devices())}

    def say(key, value):
        out[key] = value
        print(json.dumps({key: value}), flush=True)

    cell, _, engine, _, _, batches = build(args.seed, rehearse=args.rehearse,
                                           cell=args.cell)
    for _ in range(3):
        jax.block_until_ready(engine.train_batch(data_iter=batches))
    t0 = time.perf_counter()
    for _ in range(args.steps):
        loss = engine.train_batch(data_iter=batches)
    jax.block_until_ready(loss)
    say("step_ms_untraced", (time.perf_counter() - t0) / args.steps * 1e3)

    snap = get_registry().snapshot()
    say("gauges", {
        name: {",".join(f"{k}={v}" for k, v in sorted(s["labels"].items())):
               s["value"] for s in snap[name]["samples"]}
        for name in ("step_collectives", "step_collective_recv_bytes",
                     "step_collective_parse_seconds",
                     "zero_required_recv_bytes", "hbm_exec_reserved_bytes")
        if name in snap})
    compiled = engine.compiled_step()
    ledger = device_scopes.collective_ledger(compiled)
    rows = collections.defaultdict(lambda: [0, 0.0])
    for rec in ledger:
        key = (rec["op"], rec["scope"], rec["pass"], rec["n"],
               "async" if rec["done"] else "sync",
               device_scopes._NUMBERED.sub("", rec["instruction"]))
        rows[key][0] += rec["times"]
        rows[key][1] += rec["times"] * rec["recv_bytes"] / 2**20
    say("ledger", [list(k) + v for k, v in
                   sorted(rows.items(), key=lambda kv: -kv[1][1])])
    if args.rehearse:
        return

    table = engine.profile_device_scopes(batches, steps=args.steps)
    say("device_ms_a_step", table["device_ms_a_step"])
    say("collective_ms_a_step", table["collective_ms_a_step"])
    say("collectives", table["collectives"])
    say("scopes", table["scopes"][:30])
    say("no_op_name", table["no_op_name"][:12])

    # the check, on a trace of its own
    def run():
        for _ in range(args.steps):
            loss = engine.train_batch(data_iter=batches)
        jax.block_until_ready(loss)

    by_device = device_scopes.capture(run)
    pattern = re.compile(cell.config["trace_names"]["collective"])
    kind = lambda name: device_scopes._NUMBERED.sub("", name)
    mine = {name for rec in ledger
            for name in (rec["instruction"], rec["done"]) if name}
    check = {}
    for dev, events in sorted(by_device.items()):
        selfs = device_scopes._self_times(events)
        named = collections.Counter()       # what the pattern matches
        held = collections.Counter()        # what the ledger holds
        for name, _, dur in selfs:
            if pattern.search(kind(name)):
                named[kind(name)] += dur
            if name in mine:
                held[kind(name)] += dur
        ms = lambda ns: ns / args.steps / 1e6
        busy = sum(d for _, _, d in selfs)
        check[dev] = {
            "busy_ms_a_step": ms(busy),
            "trace_kinds_ms": {k: ms(v) for k, v in named.most_common()},
            "ledger_kinds_ms": {k: ms(v) for k, v in held.most_common()},
            "trace_kinds_not_in_ledger": sorted(set(named) - set(held)),
            "ledger_kinds_the_pattern_misses": sorted(
                k for k in held if not pattern.search(k)),
            "ledger_instructions_never_seen": len(
                mine - {name for name, _, _ in selfs}),
            "named_ms": ms(sum(named.values())),
            "held_and_named_ms": ms(sum(v for k, v in held.items()
                                        if pattern.search(k))),
            "held_ms": ms(sum(held.values())),
            "exposed_pct_by_name": 100 * sum(named.values()) / busy,
            "exposed_pct_by_ledger": 100 * sum(held.values()) / busy,
        }
    say("check", check)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_collectives.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
