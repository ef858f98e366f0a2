"""Multi-replica router e2e on real ContinuousBatchers
(inference/router.py): THE acceptance tests — a shared-prefix trace
routed over 2 live ReplicaServers places affinity traffic where the
cache heat is (strictly more prefix hit tokens than round-robin on the
SAME trace, byte-identical outputs), a killed replica's admitted
requests all complete via failover with zero leaks on the survivor,
the 429/503 shed/drain mapping, /cancel, and the stitched
router→replica trace under one trace id.  z-sorted: batcher compiles
run late in the tier-1 alphabetical window (the test_zspecdec
convention)."""
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.inference.router import (ReplicaServer, Router,
                                            replay_routed)
from deepspeed_tpu.inference.serving import ContinuousBatcher
from deepspeed_tpu.telemetry import fleet, loadgen, reqtrace

from .simple_model import tiny_gpt2_engine

MAX_TOKENS = 64


@pytest.fixture(scope="module")
def eng():
    mesh_mod.set_mesh(None)
    yield tiny_gpt2_engine(max_tokens=MAX_TOKENS)
    mesh_mod.set_mesh(None)


def _trace(n=10, ratio=0.6, rate=3.0, seed=0):
    # shared prefix LONGER than the 16-token page size: repeats hit one
    # full cached block (16 tokens); at ~3 req/s a gpt2-tiny request
    # finishes before the next arrives, so donated pages are in the
    # radix tree when the next shared prompt lands
    cfg = loadgen.TraceConfig(
        seed=seed, n_requests=n, arrival="poisson", rate_rps=rate,
        prompt_len_mix=((26, 1.0),), shared_prefix_ratio=ratio,
        shared_prefix_len=24, gen_len_min=2, gen_len_max=4,
        vocab_size=256, max_total_len=MAX_TOKENS)
    return loadgen.generate_trace(cfg)


def _fleet(eng, n=2, **batcher_kw):
    servers = []
    warm = np.arange(25, dtype=np.int32) % 256
    for k in range(n):
        b = ContinuousBatcher(eng, n_slots=2, prefix_cache={},
                              **batcher_kw)
        # warm BEFORE the serve loop owns the batcher: an in-loop
        # compile holds the step lock for seconds and submits would
        # time out at the router
        b.run([warm], max_new_tokens=4, ticks=2)
        b.warmup_windows(2)
        servers.append(ReplicaServer(b, ticks=2, name=f"r{k}",
                                     rank=k).start())
    return servers


def _router(servers, policy="affinity", **kw):
    kw.setdefault("block_tokens", 16)
    kw.setdefault("timeout_s", 30.0)
    return Router(replicas={s.name: s.target for s in servers},
                  policy=policy, **kw)


def _stop_all(servers):
    for s in servers:
        if not s._killed:
            s.stop()


# ----------------------------------------------------------------------
def test_affinity_beats_round_robin_hit_tokens_byte_identical(eng):
    trace = _trace()
    reports = {}
    outputs = {}
    for policy in ("affinity", "round_robin"):
        servers = _fleet(eng)
        router = _router(servers, policy=policy)
        try:
            reports[policy] = replay_routed(router, trace, None,
                                            timeout_s=240.0)
            outputs[policy] = {
                rr.rid: list(rr.result["tokens"])
                for rr in router._requests.values()
                if rr.state == "done"}
            # nothing shed, nothing lost, nothing leaked
            assert reports[policy].completed == trace.config.n_requests
            assert reports[policy].routed["lost"] == 0
            for s in servers:
                assert not any(s.batcher.leak_counts().values())
        finally:
            _stop_all(servers)
    aff = reports["affinity"].goodput["prefix_hit_token_ratio"]
    rr_ = reports["round_robin"].goodput["prefix_hit_token_ratio"]
    # the acceptance bar: prefix-affinity placement strictly beats
    # round-robin on prefix-cache hit-token ratio over the same trace
    assert aff is not None and rr_ is not None
    assert aff > rr_, (aff, rr_)
    assert reports["affinity"].routed["hit_tokens"] > \
        reports["round_robin"].routed["hit_tokens"]
    # placement must never change tokens: greedy decode is replica-
    # independent (same engine params), so both arms are byte-identical
    assert outputs["affinity"] == outputs["round_robin"]
    # per-replica rollup + replica column are present for debuggability
    rep = reports["affinity"]
    assert rep.per_replica and set(rep.per_replica) == {"r0", "r1"}
    assert sum(p["requests"] for p in rep.per_replica.values()) == \
        rep.completed
    assert any(w.get("replica") for w in rep.waterfalls)
    assert "replica" in rep.format_waterfalls(4)
    # affinity concentrated the shared-prefix family on ONE replica
    shared = [w for w in rep.waterfalls if w["shared_prefix"]
              and w.get("replica")]
    assert len({w["replica"] for w in shared}) == 1


def test_failover_zero_lost_zero_leaked_on_survivor(eng):
    servers = _fleet(eng)
    router = _router(servers, failover_after=2,
                     suspect_cooldown_s=300.0)
    try:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 256, size=(12,)).astype(np.int32)
                   for _ in range(6)]
        rids = [router.submit(p, max_new_tokens=8) for p in prompts]
        assert not router.rejected
        # kill whichever replica holds admitted work, abruptly (no
        # drain): its in-flight admitted requests must fail over
        by_rep = {}
        for rid in rids:
            by_rep.setdefault(router._requests[rid].replica,
                              []).append(rid)
        victim_name = max(by_rep, key=lambda n: len(by_rep[n]))
        victim = next(s for s in servers if s.name == victim_name)
        victim.kill()
        done = router.wait(rids, timeout_s=120.0)
        # zero lost: every admitted request completed via failover
        assert sorted(done) == sorted(rids)
        assert sum(rr.failovers
                   for rr in router._requests.values()) >= 1
        for rid, p in zip(rids, prompts):
            assert list(done[rid][:len(p)]) == list(p)
            assert len(done[rid]) > len(p)
        survivor = next(s for s in servers if s.name != victim_name)
        # give the survivor's loop a beat to finish retiring
        survivor.batcher.wait(ticks=2, timeout_s=30.0, partial=True)
        assert not any(survivor.batcher.leak_counts().values())
        assert all(rr.replica == survivor.name
                   for rr in router._requests.values())
    finally:
        _stop_all(servers)


def test_http_shed_maps_429_drain_maps_503_and_cancel(eng):
    b = ContinuousBatcher(eng, n_slots=1, prefix_cache={},
                          admission={"max_queue_depth": 2})
    srv = ReplicaServer(b, ticks=2, name="r0")    # loop NOT started:
    prompt = list(range(8))                       # the queue can't drain
    codes = [srv.submit({"prompt": prompt, "max_new_tokens": 4})[0]
             for _ in range(4)]
    assert codes[:2] == [200, 200]
    assert 429 in codes[2:]
    shed = next(p for c, p in
                [srv.submit({"prompt": prompt, "max_new_tokens": 4})]
                if c == 429)
    assert shed["shed"] == "queue_full" and "uid" in shed
    # /result on a shed uid is a terminal "shed" status, not a 404
    assert srv.result(shed["uid"])["status"] == "shed"
    # cancel a queued request: rejected outcome, reason cancelled
    first_uid = None
    for uid in list(b._queue and [b._queue[0].uid] or []):
        first_uid = uid
    assert first_uid is not None
    assert srv.cancel(first_uid) == "cancelled"
    assert srv.result(first_uid) == {"status": "shed",
                                     "reason": "cancelled"}
    # drain: remaining work forced out, endpoint sheds with 503
    srv.drain(timeout_s=30.0)
    assert not any(b.leak_counts().values())
    code, payload = srv.submit({"prompt": prompt, "max_new_tokens": 4})
    assert code == 503 and payload["shed"] == "draining"
    assert srv.health()["draining"] is True
    srv.stop()
    # bad requests are 400s, not 500s
    b2 = ContinuousBatcher(eng, n_slots=1)
    srv2 = ReplicaServer(b2, ticks=2, name="r1")
    assert srv2.submit({"prompt": []})[0] == 400
    assert srv2.submit({"prompt": list(range(MAX_TOKENS + 8)),
                        "max_new_tokens": 8})[0] == 400
    srv2.stop()


def test_stitched_trace_router_to_replica_one_trace_id(eng):
    servers = _fleet(eng, n=1)
    tracer = reqtrace.RequestTracer(sample=1)
    tracer.attach(servers[0].batcher)
    router = _router(servers)
    try:
        prompt = np.arange(20, dtype=np.int32) % 256
        rid = router.submit(prompt, max_new_tokens=4)
        done = router.wait([rid], timeout_s=120.0)
        assert rid in done
        stitched = fleet.stitch_tracez({
            "router": router.tracez(),
            "r0": tracer.payload(full=True)})
        rr = router._requests[rid]
        tr = next(t for t in stitched["traces"]
                  if t["trace_id"] == rr.ctx.trace_id)
        # router→replica spans under ONE trace id, cross-surface
        assert tr["cross_replica"] is True
        assert set(tr["replicas"]) == {"router", "r0"}
        names = {(s["replica"], s["name"]) for s in tr["spans"]}
        assert {("router", "route"), ("router", "hop"),
                ("r0", "request")} <= names
        # the replica's local root chains under the admitting hop span
        hop_ids = {s["span_id"] for s in tr["spans"]
                   if s["name"] == "hop"}
        rep_root = next(s for s in tr["spans"]
                        if s["replica"] == "r0"
                        and s["name"] == "request")
        assert rep_root["parent_id"] in hop_ids
        # and the replica-side tree carries the serving spans
        assert any(s["replica"] == "r0" and s["name"] == "prefill"
                   for s in tr["spans"])
    finally:
        tracer.detach()
        _stop_all(servers)
