#!/usr/bin/env python
"""Traffic-trace load harness CLI (telemetry/loadgen.py).

Replays a seeded, deterministic traffic trace (Poisson or bursty
arrivals, mixed prompt lengths, shared-prefix traffic, Zipf generation
lengths) against a ContinuousBatcher and reports **goodput under SLO**:
tokens/s counted only for requests meeting the TTFT/TPOT bounds, SLO
attainment %, tail percentiles, queue-depth timeline, and per-request
phase waterfalls.

Modes:

  # human-readable load run (auto-calibrated SLO, report to JSON)
  JAX_PLATFORMS=cpu python scripts/loadgen.py --seed 0 --report out.json

  # print the deterministic trace only (no model, no jax compute) —
  # running twice with the same seed must produce identical bytes
  python scripts/loadgen.py --seed 0 --emit-trace

  # CI regression gate: replay the baseline's embedded trace, fail on
  # goodput regression beyond tolerance (exit 1)
  JAX_PLATFORMS=cpu python scripts/loadgen.py \
      --gate SERVE_LOAD_BASELINE.json --report loadgen_report.json

  # (re)record the baseline after a DELIBERATE change
  JAX_PLATFORMS=cpu python scripts/loadgen.py \
      --record-baseline SERVE_LOAD_BASELINE.json

  # per-request traces: retain every measured request's span tree and
  # write Perfetto JSONs; the slowest-TTFT waterfall links each bar to
  # its trace file (open in ui.perfetto.dev)
  JAX_PLATFORMS=cpu python scripts/loadgen.py --seed 0 --trace-out traces/

  # chaos: after the clean passes, replay once more under a seeded
  # fault plan (testing/chaos.py) and report goodput-under-faults next
  # to the clean number; assert every planned fault fired, zero leaked
  # pages/slots, and a throughput floor
  JAX_PLATFORMS=cpu python scripts/loadgen.py --seed 0 \
      --chaos chaos_plan.json --chaos-assert-fired --chaos-floor 0.3

  # admission control + closed-loop clients: bounded queue, deadline
  # shedding (sheds count AGAINST attainment), client retry w/ backoff
  JAX_PLATFORMS=cpu python scripts/loadgen.py --seed 0 --admission \
      --max-queue-depth 8 --retries 2

The SLO bounds are machine-relative by default (``calibrate_slo``:
k× the box's own unloaded TTFT/TPOT), so the gate is portable across
runner speeds; pass --slo-ttft-ms/--slo-tpot-ms for absolute bounds.
The gate replays ``--passes`` times and judges the BEST pass: a one-off
box hiccup (GC, noisy neighbor) must not fail CI, a systematic
scheduling regression fails every pass.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-requests", type=int, default=24)
    ap.add_argument("--arrival", choices=["poisson", "bursty"],
                    default="poisson")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="mean arrival rate, requests/s (trace clock)")
    ap.add_argument("--burst-rate", type=float, default=None,
                    help="bursty-mode burst arrival rate (default 4x)")
    ap.add_argument("--shared-prefix-ratio", type=float, default=0.25)
    ap.add_argument("--shared-prefix-len", type=int, default=8)
    ap.add_argument("--gen-len-max", type=int, default=12)
    ap.add_argument("--max-total", type=int, default=64,
                    help="prompt+generation clamp (= engine max_tokens)")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="replay the trace at N x its recorded load")
    ap.add_argument("--model", default="gpt2-tiny")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the radix prefix cache so the trace's "
                         "shared-prefix traffic produces KV reuse hits")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--ticks", type=int, default=4)
    ap.add_argument("--slo-ttft-ms", type=float, default=None)
    ap.add_argument("--slo-tpot-ms", type=float, default=None)
    ap.add_argument("--passes", type=int, default=2,
                    help="measured replays; the report/gate uses the "
                         "best pass (rides out one-off box hiccups)")
    ap.add_argument("--waterfalls", type=int, default=8,
                    help="slowest-TTFT waterfall rows to print")
    ap.add_argument("--report", default=None,
                    help="write the full JSON report here")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="retain per-request traces during the measured "
                         "passes (telemetry/reqtrace.py) and write each "
                         "as Perfetto/Chrome-trace JSON under DIR; the "
                         "slowest-TTFT waterfall links each bar to its "
                         "trace file")
    ap.add_argument("--trace-sample", type=int, default=1,
                    help="head-sampling rate for --trace-out (1-in-N; "
                         "default 1 = retain every request, so every "
                         "waterfall bar has a trace)")
    ap.add_argument("--emit-trace", action="store_true",
                    help="print the trace JSON and exit (determinism "
                         "check: identical bytes for identical seeds)")
    ap.add_argument("--admission", action="store_true",
                    help="enable the SLO-aware admission controller "
                         "(inference/admission.py): bounded queue, "
                         "deadline shedding, degradation ladder")
    ap.add_argument("--max-queue-depth", type=int, default=16,
                    help="admission queue bound (with --admission)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="default per-request deadline (with --admission)")
    ap.add_argument("--retries", type=int, default=0,
                    help="client retry-with-jittered-backoff attempts "
                         "for shed requests (closed-loop behavior)")
    ap.add_argument("--chaos", default=None, metavar="PLAN.json",
                    help="after the clean measured passes, replay the "
                         "trace once more under this seeded fault plan "
                         "(testing/chaos.py) and report goodput-under-"
                         "faults next to the clean number")
    ap.add_argument("--chaos-floor", type=float, default=None,
                    help="fail (exit 1) when the chaos pass's total "
                         "token throughput falls below this fraction "
                         "of the clean pass's")
    ap.add_argument("--chaos-assert-fired", action="store_true",
                    help="fail (exit 1) unless every site named by the "
                         "chaos plan actually fired")
    ap.add_argument("--router", type=int, default=0, metavar="N",
                    help="replay through an in-process N-replica fleet "
                         "(inference/router.py: N ContinuousBatchers "
                         "behind ReplicaServers behind one Router) "
                         "instead of a single batcher; implies "
                         "--prefix-cache (per-replica radix caches are "
                         "what placement affinity feeds)")
    ap.add_argument("--router-policy",
                    choices=["affinity", "round_robin", "compare"],
                    default="compare",
                    help="placement policy for --router runs; 'compare' "
                         "replays the SAME trace under both and reports "
                         "prefix-affinity vs round-robin side by side")
    ap.add_argument("--router-kill", action="store_true",
                    help="failover arm (with --router): kill one "
                         "replica mid-replay and verify every admitted "
                         "request still completes via router failover "
                         "(zero lost, zero leaked pages/slots on "
                         "survivors)")
    ap.add_argument("--router-block-tokens", type=int, default=None,
                    help="router prefix-sketch block size (default: the "
                         "replica caches' page_tokens, so sketch heat "
                         "aligns with what the caches can serve)")
    ap.add_argument("--router-assert", action="store_true",
                    help="turn the --router comparison/failover "
                         "verdicts into exit-code gates (CI): affinity "
                         "must strictly beat round-robin on prefix hit-"
                         "token ratio, and the kill arm must lose zero "
                         "admitted requests")
    ap.add_argument("--gate", default=None, metavar="BASELINE.json",
                    help="regression-gate mode against this baseline")
    ap.add_argument("--record-baseline", default=None, metavar="PATH",
                    help="write a fresh baseline from this run")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="override the baseline's gate tolerance")
    return ap.parse_args(argv)


def trace_config(args, loadgen, vocab_size: int):
    return loadgen.TraceConfig(
        seed=args.seed, n_requests=args.n_requests, arrival=args.arrival,
        rate_rps=args.rate, burst_rate_rps=args.burst_rate,
        prompt_len_mix=((8, 0.6), (16, 0.4)),
        shared_prefix_ratio=args.shared_prefix_ratio,
        shared_prefix_len=args.shared_prefix_len,
        gen_len_min=2, gen_len_max=args.gen_len_max,
        vocab_size=vocab_size, max_total_len=args.max_total)


def build_engine(args):
    """gpt2-family inference engine sized for the trace (CPU-mesh
    friendly: gpt2-tiny compiles in seconds).  One engine can back
    SEVERAL batchers (the --router fleet shares it so params and the
    engine-level prefill executables exist once)."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    cfg = gpt2_config(args.model, dtype=jnp.float32)
    model = GPT2LMHeadModel(cfg)
    params = jax.tree_util.tree_map(
        lambda x: getattr(x, "value", x),
        model.init(jax.random.PRNGKey(0),
                   jnp.zeros((1, 8), jnp.int32))["params"],
        is_leaf=lambda x: hasattr(x, "names") and hasattr(x, "value"))
    eng = deepspeed_tpu.init_inference(model=model, dtype=jnp.float32,
                                       params=params,
                                       max_tokens=args.max_total)
    return eng, cfg


def build_batcher(args, eng=None):
    from deepspeed_tpu.inference.serving import ContinuousBatcher

    if eng is None:
        eng, cfg = build_engine(args)
    else:
        cfg = eng.model_cfg
    admission = None
    if getattr(args, "admission", False):
        admission = {"max_queue_depth": getattr(args, "max_queue_depth",
                                                16)}
        if getattr(args, "deadline_ms", None) is not None:
            admission["deadline_ms"] = args.deadline_ms
    return ContinuousBatcher(
        eng, n_slots=args.slots,
        prefix_cache={} if getattr(args, "prefix_cache", False) else None,
        admission=admission
    ), cfg


_CALIBRATION = {"prompt_len": 8, "max_new": 6, "runs": 3,
                "ttft_scale": 10.0, "tpot_scale": 8.0}


def run_load(args, trace_cfg, calibration=None):
    """Warm thoroughly, calibrate (or take absolute bounds), replay
    ``--passes`` times; returns (best_report, all_reports, slo,
    tracer, chaos_result).  ``calibration`` overrides ``_CALIBRATION``
    (gate mode passes the baseline's embedded dict so the gate always
    judges with the SAME SLO scaling the floors were recorded
    against).  ``tracer`` is the request tracer attached for
    ``--trace-out`` (None otherwise) — attached AFTER warmup/
    calibration, so retained traces cover exactly the measured passes.
    ``chaos_result`` (with ``--chaos``; None otherwise) is
    ``(report, fired_summary, leaks)`` from ONE extra replay of the
    same trace under the seeded fault plan — installed after the clean
    passes so warmup/calibration and the clean numbers are never
    faulted."""
    from deepspeed_tpu.telemetry import loadgen

    batcher, _ = build_batcher(args)
    trace = loadgen.generate_trace(trace_cfg)
    # warmup: the decode windows, the admission executables, and two
    # throwaway replays of the SAME trace so every (batch width, bucket)
    # prefill executable the trace can exercise is compiled before the
    # measured pass — a compile inside the run would be billed as TTFT
    batcher.run([trace.requests[0].prompt], max_new_tokens=4,
                ticks=args.ticks)
    batcher.warmup_windows(args.ticks)
    # slo=None: throwaway warmup requests must not inflate the
    # serving_slo_* counters or the /statusz met/violated tallies
    for _ in range(2):
        loadgen.replay(batcher, trace, None, ticks=args.ticks,
                       time_scale=max(args.time_scale, 8.0))
    if args.slo_ttft_ms is not None and args.slo_tpot_ms is not None:
        slo = loadgen.SLOConfig(ttft_ms=args.slo_ttft_ms,
                                tpot_ms=args.slo_tpot_ms)
    else:
        cal = loadgen.calibrate_slo(batcher,
                                    **(calibration or _CALIBRATION))
        # a single explicit bound still wins; only the missing one is
        # machine-calibrated
        slo = loadgen.SLOConfig(
            ttft_ms=cal.ttft_ms if args.slo_ttft_ms is None
            else args.slo_ttft_ms,
            tpot_ms=cal.tpot_ms if args.slo_tpot_ms is None
            else args.slo_tpot_ms)
    tracer = None
    if getattr(args, "trace_out", None):
        from deepspeed_tpu.telemetry import reqtrace

        tracer = reqtrace.RequestTracer(
            sample=max(1, getattr(args, "trace_sample", 1)),
            ring=max(256, 2 * args.n_requests * max(1, args.passes)))
        tracer.attach(batcher)
    retry = None
    if getattr(args, "retries", 0):
        retry = {"max_retries": int(args.retries), "seed": args.seed}
    reports = [loadgen.replay(batcher, trace, slo, ticks=args.ticks,
                              time_scale=args.time_scale, retry=retry)
               for _ in range(max(1, args.passes))]
    if tracer is not None:
        tracer.detach()
    best = max(reports,
               key=lambda r: (r.goodput["slo_attainment"] or 0.0,
                              r.goodput["goodput_tok_s"]))
    chaos_result = None
    if getattr(args, "chaos", None):
        from deepspeed_tpu.testing import chaos as chaos_mod

        plan = chaos_mod.ChaosPlan.load(args.chaos)
        engine = chaos_mod.install_plan(plan)
        try:
            chaos_report = loadgen.replay(
                batcher, trace, slo, ticks=args.ticks,
                time_scale=args.time_scale, retry=retry)
        finally:
            fired = engine.summary()
            chaos_mod.clear()
        chaos_result = (chaos_report, fired, batcher.leak_counts())
    return best, reports, slo, tracer, chaos_result


def _build_fleet(args, eng, n, trace, ticks):
    """N fresh batchers (own radix prefix cache each — per-replica
    cache heat is the signal being measured) behind started
    ReplicaServers; each batcher warmed before its server loop runs."""
    import numpy as np

    from deepspeed_tpu.inference.router import ReplicaServer
    from deepspeed_tpu.inference.serving import ContinuousBatcher

    # a NEUTRAL warm prompt, deliberately not a trace prompt: warming
    # with a shared-prefix member would pre-seed the shared prefix into
    # EVERY replica's radix cache and erase the very affinity-vs-round-
    # robin difference being measured.  Same length bucket as the trace
    # prompts so the prefill executables still pre-compile.
    warm_len = max(len(r.prompt) for r in trace.requests)
    warm = (np.arange(warm_len, dtype=np.int32) * 7 + 3) \
        % trace.config.vocab_size
    admission = None
    if getattr(args, "admission", False):
        # --admission applies per REPLICA (each batcher runs its own
        # controller) — routed 429s then exercise the shed→next-rung
        # path for real
        admission = {"max_queue_depth": getattr(args, "max_queue_depth",
                                                16)}
        if getattr(args, "deadline_ms", None) is not None:
            admission["deadline_ms"] = args.deadline_ms
    servers = []
    for k in range(n):
        b = ContinuousBatcher(eng, n_slots=args.slots, prefix_cache={},
                              admission=dict(admission)
                              if admission else None)
        b.run([warm], max_new_tokens=4, ticks=ticks)
        b.warmup_windows(ticks)
        servers.append(ReplicaServer(b, ticks=ticks, name=f"r{k}",
                                     rank=k).start())
    return servers


def run_router_mode(args) -> int:
    """--router N: replay the trace through an in-process N-replica
    fleet and report prefix-affinity vs round-robin placement (hit-
    token ratio, TTFT p99, goodput) plus the kill-one-replica failover
    arm.  Fresh batchers per arm — arms must not inherit each other's
    cache heat or the comparison is meaningless."""
    from deepspeed_tpu.inference.router import Router, replay_routed
    from deepspeed_tpu.telemetry import loadgen

    n = max(2, args.router)
    args.prefix_cache = True          # affinity routes AT the caches
    # flags the routed path does not implement must fail or warn, never
    # silently report clean numbers the user believes were faulted
    unsupported = [f for f, v in (("--chaos", args.chaos),
                                  ("--retries", args.retries),
                                  ("--trace-out", args.trace_out),
                                  ("--gate", args.gate))
                   if v]
    if unsupported:
        print(f"error: {', '.join(unsupported)} not supported with "
              f"--router (the router has its own retry ladder; chaos/"
              f"trace-out/gate cover the single-batcher path)",
              file=sys.stderr)
        return 2
    cfg = trace_config(args, loadgen, vocab_size=512)
    if args.shared_prefix_len < 17 and args.router_block_tokens is None:
        print(f"note: --shared-prefix-len {args.shared_prefix_len} is "
              f"below the replica caches' 16-token page size — shared "
              f"prompts will produce ZERO cache hits and the affinity/"
              f"round-robin comparison will be vacuous; use "
              f"--shared-prefix-len >= 17")
    trace = loadgen.generate_trace(cfg)
    eng, _ = build_engine(args)

    # calibrate once on a throwaway single batcher (machine-relative
    # SLO bounds, the run_load discipline)
    if args.slo_ttft_ms is not None and args.slo_tpot_ms is not None:
        slo = loadgen.SLOConfig(ttft_ms=args.slo_ttft_ms,
                                tpot_ms=args.slo_tpot_ms)
    else:
        cal_b, _ = build_batcher(args, eng)
        cal_b.run([trace.requests[0].prompt], max_new_tokens=4,
                  ticks=args.ticks)
        cal_b.warmup_windows(args.ticks)
        cal = loadgen.calibrate_slo(cal_b, **_CALIBRATION)
        slo = loadgen.SLOConfig(
            ttft_ms=cal.ttft_ms if args.slo_ttft_ms is None
            else args.slo_ttft_ms,
            tpot_ms=cal.tpot_ms if args.slo_tpot_ms is None
            else args.slo_tpot_ms)

    def run_arm(policy, kill=False):
        servers = _build_fleet(args, eng, n, trace, args.ticks)
        bt = args.router_block_tokens
        if bt is None:
            pc = servers[0].batcher.prefix_cache
            bt = pc.page_tokens if pc is not None else 16
        router = Router(
            replicas={s.name: s.target for s in servers},
            policy=policy, block_tokens=bt, seed=args.seed)
        kill_fn = None
        kill_at = None
        if kill:
            # kill the replica that holds the most admitted in-flight
            # work at trigger time — killing an idle one proves nothing
            kill_at = 2

            def kill_fn():
                per = router.per_replica()
                name = max(per, key=lambda n: per[n]["in_flight"])
                next(s for s in servers if s.name == name).kill()
        try:
            report = replay_routed(router, trace, slo,
                                   time_scale=args.time_scale,
                                   kill_at=kill_at, kill_fn=kill_fn)
        finally:
            leaks = {s.name: s.batcher.leak_counts()
                     for s in servers if not s._killed}
            for s in servers:
                if not s._killed:
                    s.stop()
        report.routed["leaks"] = leaks
        return report

    arms = {}
    policies = ["affinity", "round_robin"] \
        if args.router_policy == "compare" else [args.router_policy]
    for policy in policies:
        print(f"\n=== routed replay: {n} replicas, policy={policy} ===")
        arms[policy] = run_arm(policy)
        print(arms[policy].table())
        print(arms[policy].format_waterfalls(args.waterfalls))
    if args.router_kill:
        print(f"\n=== failover arm: {n} replicas, kill r{n - 1} "
              f"mid-replay ===")
        arms["failover"] = run_arm("affinity", kill=True)
        print(arms["failover"].table())
        print(arms["failover"].format_waterfalls(args.waterfalls))

    rc = 0
    verdict = {}
    if "affinity" in arms and "round_robin" in arms:
        a = arms["affinity"].goodput.get("prefix_hit_token_ratio") or 0.0
        r = arms["round_robin"].goodput.get("prefix_hit_token_ratio") \
            or 0.0
        verdict["affinity_hit_token_ratio"] = a
        verdict["round_robin_hit_token_ratio"] = r
        verdict["affinity_beats_round_robin"] = a > r
        print(f"\nprefix hit-token ratio: affinity {a:.4f} vs "
              f"round-robin {r:.4f} -> "
              f"{'affinity WINS' if a > r else 'NO WIN'}")
        print(f"TTFT p99: affinity "
              f"{arms['affinity'].goodput['ttft_p99_ms']:.1f} ms vs "
              f"round-robin "
              f"{arms['round_robin'].goodput['ttft_p99_ms']:.1f} ms")
        if args.router_assert and not a > r:
            print("ROUTER FAIL: affinity placement did not strictly "
                  "beat round-robin on prefix hit-token ratio",
                  file=sys.stderr)
            rc = 1
    if "failover" in arms:
        fo = arms["failover"].routed
        verdict["failover_lost"] = fo["lost"]
        verdict["failover_failovers"] = fo["failovers"]
        verdict["failover_leaks"] = fo["leaks"]
        leaked = any(any(v.values()) for v in fo["leaks"].values())
        print(f"failover: {fo['failovers']} request(s) re-placed, "
              f"{fo['lost']} lost, survivor leaks {fo['leaks']}")
        if args.router_assert and (fo["lost"] or leaked
                                   or fo["failovers"] < 1):
            print(f"ROUTER FAIL: failover arm lost {fo['lost']} "
                  f"admitted request(s) / leaked {fo['leaks']} / "
                  f"{fo['failovers']} failovers", file=sys.stderr)
            rc = 1
    if args.report:
        payload = {name: rep.to_jsonable() for name, rep in arms.items()}
        payload["verdict"] = verdict
        payload["runner"] = {"model": args.model, "slots": args.slots,
                             "ticks": args.ticks, "replicas": n,
                             "argv": sys.argv[1:]}
        d = os.path.dirname(args.report)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.report, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"routed report written: {args.report}")
    print("routed replay: " + ("PASS" if rc == 0 else "FAIL"))
    return rc


def write_traces(out_dir, tracer):
    """Write every retained request trace as Perfetto/Chrome-trace JSON
    (one file per trace, the same event format/time axis as
    ``DSTPU_TRACE`` process spans) plus an ``index.json``; returns
    {uid: file path} for the waterfall links."""
    from deepspeed_tpu.telemetry import reqtrace

    os.makedirs(out_dir, exist_ok=True)
    links = {}
    for tr in tracer.traces():
        name = f"reqtrace_uid{tr['uid']}_{tr['trace_id'][:12]}.json"
        path = os.path.join(out_dir, name)
        reqtrace.save_chrome_trace(path, tr)
        # first (newest) retention wins: passes re-submit the same
        # workload under fresh uids, so collisions only happen across
        # tracer reuse — keep the newest
        links.setdefault(tr["uid"], path)
    index_path = os.path.join(out_dir, "index.json")
    with open(index_path, "w") as fh:
        json.dump({"files": {str(u): p for u, p in links.items()},
                   **tracer.index()}, fh, indent=1)
    print(f"retained request traces: {len(links)} files under {out_dir} "
          f"(index: {index_path})")
    return links


def chaos_verdict(args, clean_report, chaos_result) -> int:
    """Print goodput-under-faults next to the clean pass and apply the
    ``--chaos-floor`` / ``--chaos-assert-fired`` gates; returns the
    exit code (0 = pass).  With ``--report`` the file holds BOTH
    passes ({"clean", "chaos", "fired", "leaks", "verdict"}) — a CI
    artifact named for the chaos run must actually contain the faulted
    numbers and the fired-fault log, not just the clean pass."""
    chaos_report, fired, leaks = chaos_result
    gc_, gf = clean_report.goodput, chaos_report.goodput
    print()
    print("=== goodput under faults (seeded chaos plan) ===")
    print(chaos_report.table())
    ratio = (gf["total_tok_s"] / gc_["total_tok_s"]
             if gc_["total_tok_s"] else None)
    print(f"clean vs faulted throughput: {gc_['total_tok_s']:.1f} -> "
          f"{gf['total_tok_s']:.1f} tok/s"
          + (f" (x{ratio:.3f})" if ratio is not None else ""))
    print(f"clean vs faulted attainment: "
          f"{100.0 * (gc_['slo_attainment'] or 0.0):.1f}% -> "
          f"{100.0 * (gf['slo_attainment'] or 0.0):.1f}%")
    print(f"faults fired: {fired['fired']} "
          f"(events: {[(e['site'], e['invocation']) for e in fired['fired_events']]})")
    print(f"leaks after faulted trace: {leaks}")
    rc = 0
    if any(leaks.values()):
        print(f"CHAOS FAIL: leaked resources after the faulted trace: "
              f"{leaks}", file=sys.stderr)
        rc = 1
    if getattr(args, "chaos_assert_fired", False):
        missing = set(fired["planned_sites"]) - set(fired["fired"])
        if missing:
            print(f"CHAOS FAIL: planned sites never fired: "
                  f"{sorted(missing)}", file=sys.stderr)
            rc = 1
        else:
            print(f"chaos: every planned site fired "
                  f"({fired['planned_sites']})")
    floor = getattr(args, "chaos_floor", None)
    if floor is not None and ratio is not None:
        if ratio < floor:
            print(f"CHAOS FAIL: faulted throughput ratio {ratio:.3f} < "
                  f"floor {floor}", file=sys.stderr)
            rc = 1
        else:
            print(f"chaos: throughput ratio {ratio:.3f} >= floor {floor}")
    print("chaos replay: " + ("PASS" if rc == 0 else "FAIL"))
    if getattr(args, "report", None):
        payload = {
            "clean": clean_report.to_jsonable(),
            "chaos": chaos_report.to_jsonable(),
            "fired": fired, "leaks": leaks,
            "throughput_ratio": ratio,
            "verdict": "PASS" if rc == 0 else "FAIL",
            "runner": {"model": args.model, "slots": args.slots,
                       "ticks": args.ticks, "argv": sys.argv[1:]},
        }
        d = os.path.dirname(args.report)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.report, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"clean+chaos report written: {args.report}")
    return rc


def write_report(path, report, args):
    out = report.to_jsonable()
    out["runner"] = {"model": args.model, "slots": args.slots,
                     "ticks": args.ticks, "passes": args.passes,
                     "time_scale": args.time_scale,
                     "argv": sys.argv[1:]}
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
    print(f"report written: {path}")
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    from deepspeed_tpu.telemetry import loadgen

    if args.emit_trace:
        # no model, no device work: the determinism contract is
        # checkable by diffing two invocations' stdout
        cfg = trace_config(args, loadgen, vocab_size=512)
        trace = loadgen.generate_trace(cfg)
        print(json.dumps({"sha256": trace.sha256(),
                          **trace.to_jsonable()},
                         sort_keys=True, indent=1))
        return 0

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()      # before the first jit

    if args.router:
        return run_router_mode(args)

    if args.gate:
        with open(args.gate) as fh:
            baseline = json.load(fh)
        trace_cfg = loadgen.trace_config_from_dict(
            baseline["trace_config"])
        for field in ("model", "slots", "ticks", "prefix_cache"):
            if field in baseline:
                setattr(args, field, baseline[field])
        args.max_total = trace_cfg.max_total_len or args.max_total
        trace = loadgen.generate_trace(trace_cfg)
        if trace.sha256() != baseline.get("trace_sha256"):
            print(f"GATE FAIL: generated trace sha {trace.sha256()} != "
                  f"baseline {baseline.get('trace_sha256')} — the "
                  f"generator or config drifted; re-record deliberately",
                  file=sys.stderr)
            return 1
        best, reports, slo, tracer, chaos_result = run_load(
            args, trace_cfg, calibration=baseline.get("calibration"))
        print(best.table())
        if args.trace_out and tracer is not None:
            links = write_traces(args.trace_out, tracer)
            print(best.format_waterfalls(args.waterfalls, links=links))
        report_json = best.to_jsonable()
        if args.report:
            report_json = write_report(args.report, best, args)
        ok, msgs = loadgen.check_baseline(report_json, baseline,
                                          tolerance=args.tolerance)
        for m in msgs:
            print(("GATE FAIL: " if not ok and
                   ("regression" in m or "drift" in m) else "gate: ") + m)
        attains = [r.goodput["slo_attainment"] for r in reports]
        print(f"gate: per-pass attainment {attains} (best pass judged)")
        print("serving-load gate: " + ("PASS" if ok else "FAIL"))
        rc = 0 if ok else 1
        if chaos_result is not None:
            # --gate + --chaos: the faulted replay gates too (it ran —
            # ignoring its verdict would make the flags silently inert)
            rc = max(rc, chaos_verdict(args, best, chaos_result))
        return rc

    cfg = trace_config(args, loadgen, vocab_size=512)
    best, reports, slo, tracer, chaos_result = run_load(args, cfg)
    print(best.table())
    print()
    links = None
    if args.trace_out and tracer is not None:
        links = write_traces(args.trace_out, tracer)
    print(best.format_waterfalls(args.waterfalls, links=links))
    if args.report and chaos_result is None:
        write_report(args.report, best, args)    # chaos_verdict writes
    if chaos_result is not None:                 # the combined report
        rc = chaos_verdict(args, best, chaos_result)
        if rc:
            return rc
    if args.record_baseline:
        g = best.goodput
        baseline = {
            "comment": "serving-load regression baseline — recorded by "
                       "scripts/loadgen.py --record-baseline; floors are "
                       "the recorded pass minus a 0.2 margin (SLO bounds "
                       "are machine-calibrated, so floors transfer "
                       "across runner speeds)",
            "model": args.model, "slots": args.slots, "ticks": args.ticks,
            "prefix_cache": bool(args.prefix_cache),
            "trace_config": best.trace_config,
            "trace_sha256": best.trace_sha256,
            "total_output_tokens": g["total_output_tokens"],
            "slo_attainment_min":
                round(max(0.5, (g["slo_attainment"] or 0.0) - 0.2), 3),
            "goodput_token_ratio_min":
                round(max(0.5, (g["goodput_token_ratio"] or 0.0) - 0.2),
                      3),
            "tolerance": 0.15,
            "calibration": dict(_CALIBRATION),
            "recorded": {"slo": g["slo"],
                         "slo_attainment": g["slo_attainment"],
                         "goodput_tok_s": g["goodput_tok_s"],
                         "goodput_token_ratio": g["goodput_token_ratio"],
                         "ttft_p99_ms": g["ttft_p99_ms"],
                         "tpot_p99_ms": g["tpot_p99_ms"]},
        }
        with open(args.record_baseline, "w") as fh:
            json.dump(baseline, fh, indent=1)
            fh.write("\n")
        print(f"baseline written: {args.record_baseline}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
