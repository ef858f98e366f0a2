"""Drive the server: ``init_inference`` → ``ContinuousBatcher`` under an
open loop, for ``--seconds``.

Set-up: weights on the device from the seed in the served type, the
batcher, one pass through every executable the run will use (decode
windows, prefill widths x power-of-two chunks, and the prompt lengths of
this seed's own trace), then a lead-in of the same mix until the slots are
in steady occupancy.  Window: every request is submitted to the batcher
when it is *due*, whatever the server is doing; every time is taken
against the due time.  After the window the batcher is forced empty (leak
check) and a seeded sample of finished requests is compared with the plain
reference.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import flops, loadgen

# Batched prefills are warmed 1 to 4 rows wide at every chunk.  A prefill is
# as wide as the slots that came free in one step: one or two in a busy
# server, more only in the first steps of the lead-in; a wider one builds
# its executables where it meets them (inside the window: window_compiles).
_WARM_WIDTHS = 4
_COUNTERS = ("prefix_cache_hit_tokens_total", "prefix_cache_miss_tokens_total",
             "serving_gather_pages_total")


def build(ctx):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ContinuousBatcher
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    conf = ctx.sized(ctx.cell.config)
    cfg = GPT2Config(vocab_size=conf["vocab_size"],
                     n_positions=conf["n_positions"], n_embd=conf["n_embd"],
                     n_layer=conf["n_layer"], n_head=conf["n_head"],
                     layer_norm_epsilon=conf["layer_norm_epsilon"])
    model = GPT2LMHeadModel(cfg)
    # one jitted call from the seed, cast to the served type inside it
    params = jax.jit(lambda r: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16),
        model.init(r, np.zeros((1, 8), np.int32))["params"]))(
            jax.random.PRNGKey(ctx.seed % (2**31 - 1)))
    sv = conf["serving"]
    eng = deepspeed_tpu.init_inference(
        model=model, params=params, max_tokens=sv["max_tokens"],
        prefix_cache={"page_tokens": sv["page_tokens"],
                      "n_pages": sv["n_pages"]})
    del params
    batcher = ContinuousBatcher(eng, n_slots=sv["n_slots"])
    return eng, batcher, cfg, sv


def _run_out(batcher, ticks=1):
    while batcher.pending:
        batcher.step(ticks=ticks)


def warm_shapes(ctx, batcher, sv, requests, prefix, vocab: int) -> None:
    """Run every executable the run will use once, with requests of their
    own.  The decode window is keyed by its length, 1 to ``step_ticks``.
    A prefill executable is keyed by (rows, chunk), a chunk being a power
    of two up to the generation limit: a group of equal lengths (as a rule
    one request) goes through the binary decomposition of its length, a
    group of unequal lengths through one padded bucket.  Widths 1 to
    ``_WARM_WIDTHS`` are run here at every chunk; first-token
    sampling and placement are keyed by rows as well.  The equal-length
    path also slices the prompt on the device, one small executable for
    each (rows, length, chunk offset), so the prompt lengths of this seed's
    own ``requests`` (known from the seed, like everything the run offers)
    are run here too, one request each, behind the system prompt
    ``prefix`` where the request shares it.  What the run still builds inside its
    window is counted, and reported as ``window_compiles``."""
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 3])
    limit = int(batcher.engine._gen_limit)
    top = limit - 2                        # longest prompt with 2 new tokens
    chunks = [1 << k for k in range(limit.bit_length()) if (1 << k) < limit]

    def go(prompts, new=2):
        for p in prompts:
            batcher.submit(p, max_new_tokens=new)
        _run_out(batcher)

    def rand(n):
        return rng.integers(0, vocab, n).astype(np.int32)

    # the decode windows first: they are the largest programs, and a
    # configuration the chip cannot hold should fail before minutes of
    # prefill compiles
    for ticks in range(1, int(sv["step_ticks"]) + 1):
        batcher.submit(rand(8), max_new_tokens=3 * int(sv["step_ticks"]))
        while batcher.pending:
            batcher.step(ticks=ticks)
    go([rand(c) for c in chunks])           # one row, every chunk
    for rows in range(2, min(_WARM_WIDTHS, int(sv["n_slots"])) + 1):
        for bucket in chunks + [limit]:
            hi = min(bucket, top)
            # buckets 1 and 2 hold one length each: an equal-length group
            lo = hi - 1 if bucket >= 4 else hi
            go([rand(hi)] + [rand(lo) for _ in range(rows - 1)])
    ctx.log(f"widths and chunks warmed ({ctx.compiles} executables so far)")
    # this seed's own prompt lengths: max_new_tokens 1 finishes a request
    # at its first token, so each costs one prefill and no decode tick
    def shared(n):
        return np.concatenate([prefix, rand(n - len(prefix))])

    if len(prefix):
        go([shared(len(prefix) + 1)], new=1)    # the prefix's pages, cached
    seen = set()
    for q in requests:
        n = len(q.prompt)
        if (q.shared_prefix, n) not in seen:
            seen.add((q.shared_prefix, n))
            go([shared(n) if q.shared_prefix else rand(n)], new=1)
        if (False, n) not in seen:
            # the same length with no hit: its prefix pages may be evicted
            seen.add((False, n))
            go([rand(n)], new=1)


class Recorder:
    """Per-request records from the batcher's lifecycle observer."""

    def __init__(self):
        self.by_uid = {}

    def __call__(self, t, uid, event, extra):
        r = self.by_uid.get(uid)
        if r is None:
            return
        if event == "prefill_start":
            r["prefill_start"] = t
            r["hit_tokens"] = extra.get("hit_tokens", 0)
            r["prefill_batch"] = extra.get("batch", 1)
        elif event == "first_token":
            r["first_token"] = r["last_emit"] = t
            r["n_out"] = 1
            r["emits"].append((t, 1))
        elif event == "emit":
            r["last_emit"] = t
            r["n_out"] += extra["n"]
            r["emits"].append((t, extra["n"]))
        elif event == "retire":
            r["retired"] = t
            r["n_retired"] = extra.get("n_out")


class Pump:
    """The open loop: submit to the batcher whatever is due, step the
    batcher, sleep only when there is nothing to do.  The batcher's own
    queue is the only queue."""

    def __init__(self, ctx, batcher, rec, ticks: int):
        self.ctx, self.batcher, self.rec = ctx, batcher, rec
        self.ticks = int(ticks)

    def backlog(self) -> int:
        """Requests waiting for a prefill or, prefilled, for a slot."""
        return len(self.batcher._queue) + len(self.batcher._parked)

    def run(self, reqs, seconds: float, tag: str):
        """Returns (t_start, t_end, records of the requests due here)."""
        ctx, b = self.ctx, self.batcher
        records = []
        t_start = time.perf_counter()
        i, n = 0, len(reqs)
        while True:
            now = time.perf_counter() - t_start
            if now >= seconds:
                break
            if i < n and reqs[i].arrival_s <= now:
                with ctx.span("submit"):
                    while i < n and reqs[i].arrival_s <= now:
                        q = reqs[i]
                        r = {"tag": tag, "idx": q.idx,
                             "due": t_start + q.arrival_s,
                             "submit": time.perf_counter(),
                             "asked": q.max_new_tokens,
                             "prompt_len": len(q.prompt), "emits": [],
                             "n_out": 0}
                        records.append(r)
                        try:
                            uid = b.submit(q.prompt,
                                           max_new_tokens=q.max_new_tokens)
                        except Exception as e:  # counted as failed, not fatal
                            r["failed"] = repr(e)
                        else:
                            r["uid"] = uid
                            self.rec.by_uid[uid] = r
                        i += 1
            if b.pending:
                with ctx.span("batcher.step"):
                    b.step(ticks=self.ticks)
            else:
                with ctx.span("sleep"):
                    nxt = reqs[i].arrival_s if i < n else seconds
                    time.sleep(max(0.0, min(nxt - now, 0.002)))
        t_end = time.perf_counter()
        # due inside the window but the loop never got back to them
        for q in reqs[i:]:
            if q.arrival_s < seconds:
                records.append({"tag": tag, "idx": q.idx,
                                "due": t_start + q.arrival_s, "emits": [],
                                "n_out": 0, "asked": q.max_new_tokens,
                                "prompt_len": len(q.prompt)})
        return t_start, t_end, records


def check_outputs(ctx, batcher, eng, cfg, reference, records) -> None:
    """A seeded sample of finished requests against the reference's full
    float32 forward: at every emitted position the emitted token's
    reference logit is within the stated tolerance of the reference's
    maximum there."""
    import jax

    tol = ctx.cell.config["reference_check"]
    done = [r for r in records if r.get("uid") in batcher._finished]
    ctx.check(len(done) >= 1, "no request finished")
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 4])
    pick = rng.permutation(len(done))[:int(tol["sample"])]
    limit = int(eng._gen_limit)
    worst = 0.0
    for k in pick:
        r = done[int(k)]
        toks = np.asarray(batcher._finished[r["uid"]], np.int32)
        n_new = len(toks) - r["prompt_len"]
        ids = np.zeros((1, limit), np.int32)
        ids[0, :len(toks)] = toks          # causal: the padding changes nothing
        lg = np.asarray(jax.device_get(reference.logits(
            eng.params, ids, n_layer=cfg.n_layer, n_head=cfg.n_head,
            vocab_size=cfg.vocab_size, eps=cfg.layer_norm_epsilon)[0]))
        at = np.arange(r["prompt_len"] - 1, len(toks) - 1)
        deficit = lg[at].max(-1) - lg[at, toks[at + 1]]
        worst = max(worst, float(deficit.max()))
        ctx.check(n_new >= 1 and float(deficit.max()) <= tol["logit_abs_tol"],
                  f"request {r['idx']}: an emitted token is {deficit.max():.4f} "
                  f"below the reference's best logit (tolerance "
                  f"{tol['logit_abs_tol']}) at emitted position "
                  f"{int(deficit.argmax())} of {n_new}")
    ctx.log(f"reference check on {len(pick)} finished requests: worst "
            f"deficit {worst:.4f} (tolerance {tol['logit_abs_tol']})")


def run(ctx, reference) -> dict:
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report
    from deepspeed_tpu.telemetry import registry

    mix = ctx.sized(ctx.cell.traffic)
    eng, batcher, cfg, sv = build(ctx)
    ctx.check(batcher.paged is not None, "paged decode did not resolve")
    ctx.log("server built")
    ticks = int(sv["step_ticks"])
    seconds = ctx.window_seconds
    lead = loadgen.serve_trace(mix, ctx.seed, float(mix["lead_in_s"]),
                               cfg.vocab_size, lead_in=True)
    reqs = loadgen.serve_trace(mix, ctx.seed, seconds, cfg.vocab_size)
    ctx.log(f"trace {loadgen.trace_sha256(reqs)[:12]}: {len(reqs)} requests, "
            f"mean prompt {np.mean([len(q.prompt) for q in reqs]):.1f}, "
            f"mean output asked {np.mean([q.max_new_tokens for q in reqs]):.1f}")
    shared = [q for q in lead + reqs if q.shared_prefix]
    prefix = shared[0].prompt[:int(mix["shared_prefix_len"])] if shared \
        else np.zeros(0, np.int32)
    # the queue is first come first served: the requests the server can
    # reach are the first of lead-in and window together
    warm_shapes(ctx, batcher, sv, (lead + reqs)[:int(mix["warm_requests"])],
                prefix, cfg.vocab_size)
    ctx.log(f"shapes warmed ({ctx.compiles} executables so far)")

    rec = Recorder()
    batcher.add_lifecycle_observer(rec)
    ctx.start_trace()
    pump = Pump(ctx, batcher, rec, ticks)
    with ctx.span("lead_in"):
        _, _, lead_records = pump.run(lead, float(mix["lead_in_s"]), "lead")
    compiles0 = ctx.compiles
    count0 = {c: registry.counter(c).total() for c in _COUNTERS}
    ticks0 = registry.counter("serving_decode_ticks_total").total()
    setup_s = time.perf_counter() - ctx.t_process
    with ctx.span("window"):
        t0, t1, records = pump.run(reqs, seconds, "win")
    ctx.stop_trace()
    window_s = t1 - t0
    compiles_in_window = ctx.compiles - compiles0
    moved = {c: registry.counter(c).total() - v for c, v in count0.items()}
    decode_ticks = registry.counter("serving_decode_ticks_total").total() - ticks0
    backlog = pump.backlog()
    active_end = sum(s is not None for s in batcher._slots)
    live_tokens = _mean_live_tokens(rec.by_uid.values(), t0, t1)
    ctx.log(f"window {window_s:.3f}s: {len(records)} due, backlog {backlog}, "
            f"{active_end} slots busy, counters {moved}")

    # tokens by emission time, whoever asked for them
    tokens_out = sum(n for r in rec.by_uid.values() for t, n in r["emits"]
                     if t0 <= t < t1)
    # every request the window saw, as it stood at the window's end: most
    # of what a saturated window serves was due during the lead-in
    at_end = [dict(r, **_cut(r, t1)) for r in lead_records + records]

    summary = batcher.drain(timeout_s=0.0, flush=False)
    leaks = batcher.leak_counts()
    failed = sum(_failed(r, batcher.rejected, t1)
                 for r in lead_records + records)
    ctx.check(failed == 0, f"{failed} requests raised, were rejected or "
                           f"retired short")
    ctx.check(not any(leaks.values()), f"leaks after the drain: {leaks}")
    if compiles_in_window:
        ctx.log(f"{compiles_in_window} executables were built or fetched "
                f"inside the window (reported as window_compiles)")
    ctx.check(moved["serving_gather_pages_total"] == 0,
              "paged serving gathered pages")
    ctx.check(registry.counter("decode_fused_fallback_total").total() == 0,
              "fused decode fell back")
    if not ctx.rehearse:
        impls = {(s, i) for s, i, _, n in dispatch_report() if n}
        for want in ctx.cell.config["expect_dispatch"]:
            ctx.check(tuple(want) in impls,
                      f"dispatch site {want[0]} never resolved to {want[1]}")
        for c in ("decode_fused_qkv_traces_total",
                  "decode_fused_post_attn_traces_total"):
            ctx.check(registry.counter(c).total() > 0, f"{c} is zero: a "
                      f"decode megakernel never engaged")
    check_outputs(ctx, batcher, eng, cfg, reference, lead_records + records)

    kv_tok = flops.kv_bytes_per_token(cfg.n_embd, cfg.n_layer)
    return {
        "setup_s": setup_s, "window_s": window_s,
        "attempted": len(records), "failed": failed,
        "compiles_in_window": compiles_in_window,
        "counts": {"due": len(records), "tokens_out": tokens_out,
                   "backlog_end": backlog,
                   "drain": summary, "hit_tokens":
                       moved["prefix_cache_hit_tokens_total"],
                   "miss_tokens": moved["prefix_cache_miss_tokens_total"]},
        "end_to_end": {"serve_tokens_per_s": tokens_out / window_s},
        "observed": {
            "records": at_end, "window": (t0, t1), "tokens_out": tokens_out,
            "backlog_end": backlog, "counters": moved,
            "window_compiles": compiles_in_window,
            "decode_ticks": decode_ticks, "n_slots": int(sv["n_slots"]),
            "mean_live_kv_tokens": live_tokens, "kv_bytes_per_token": kv_tok,
            "weight_bytes": 2 * flops.matmul_params(
                cfg.n_embd, cfg.n_layer, cfg.vocab_size),
            "decode_module": ctx.cell.config["trace_names"]["decode_module"],
        },
    }


def _cut(r: dict, t_end: float) -> dict:
    """The record as it stood at ``t_end``: tokens emitted later are not
    this window's."""
    emits = [(t, n) for t, n in r.get("emits", ()) if t < t_end]
    return {"n_out": sum(n for _, n in emits),
            "last_emit": emits[-1][0] if emits else None}


def _failed(r: dict, rejected: dict, t_end: float) -> bool:
    """Raised at submit, rejected by the server, or retired inside the
    window with fewer tokens than asked.  What the forced drain after the
    window cuts short is backlog, not failure."""
    if r.get("failed"):
        return True
    if rejected.get(r.get("uid")) not in (None, "drain_timeout"):
        return True
    return (r.get("n_retired") is not None and r.get("retired", t_end) < t_end
            and r["n_retired"] < r["asked"])


def _mean_live_tokens(records, t0: float, t1: float) -> float:
    """Time-average over the window of the context positions held by
    requests that are decoding: what a tick's attention must read."""
    total = 0.0
    for r in records:
        a, b = r.get("first_token"), r.get("retired", r.get("last_emit"))
        if a is None or b is None:
            continue
        lo, hi = max(a, t0), min(b, t1)
        if hi > lo:
            total += (hi - lo) * (r["prompt_len"] + 0.5 * r["n_out"])
    return total / (t1 - t0)
