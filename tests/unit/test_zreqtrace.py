"""Request-tracing e2e on a real ContinuousBatcher (telemetry/reqtrace
+ serving wiring): one request's span tree reconstructed from a live
``/tracez``, tail promotion past 1-in-1000 head sampling, the two-
exporter fleet stitch over a propagated traceparent, the queue-wait
histogram, and the flight-dump embedding.  z-sorted: batcher compiles
run late in the tier-1 alphabetical window (the test_zspecdec
convention)."""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.inference.serving import ContinuousBatcher
from deepspeed_tpu.telemetry import (anomaly, exporter, fleet, flightrec,
                                     goodput, registry, reqtrace)

from .simple_model import tiny_gpt2_engine

MAX_TOKENS = 48


@pytest.fixture(autouse=True)
def _fresh_anomaly(monkeypatch):
    """Fresh module anomaly engine per test (the ``test_zadmission``
    fixture): retirement promotes ALERT-COINCIDENT traces, so an alert
    another suite left active on the process singleton (the
    ``test_zattribution`` induced SLO burn was the observed source)
    would promote every trace here and break the sampling/retention
    assertions.  A fresh goodput tracker with it: the singleton's wall
    clock runs since the worker's first span, so late in a tier-1 run it
    is past ``goodput_drop``'s warm-up with a ratio under its floor, and
    the alert fires on the fresh engine's second observation (~1 s): a
    test that takes longer than that on a loaded machine retained every
    trace as ``alert`` (seen at PR 45)."""
    monkeypatch.setattr(anomaly, "_default", anomaly.AnomalyEngine())
    monkeypatch.setattr(goodput, "_default", goodput.GoodputTracker())
    yield


@pytest.fixture(scope="module")
def eng():
    mesh_mod.set_mesh(None)
    yield tiny_gpt2_engine(max_tokens=MAX_TOKENS)
    mesh_mod.set_mesh(None)


def _batcher(eng, **kw):
    return ContinuousBatcher(eng, n_slots=2, seed=0, **kw)


def _drain(b, uids, ticks=2):
    while any(u not in b._finished for u in uids):
        b.step(ticks=ticks)


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as r:
        return json.loads(r.read().decode())


def test_e2e_span_tree_reconstructs_request_via_tracez(eng):
    b = _batcher(eng)
    tracer = reqtrace.RequestTracer(sample=1, ring=16, seed=0)
    tracer.attach(b)
    ex = exporter.TelemetryExporter(port=0, tracer=tracer).start()
    try:
        uid = b.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=6)
        _drain(b, [uid])
        idx = _get(f"{ex.url}/tracez")
        assert idx["enabled"] and idx["sample"] == 1
        summ = next(s for s in idx["retained"] if s["uid"] == uid)
        tr = _get(f"{ex.url}/tracez?trace_id={summ['trace_id']}")
        names = [s["name"] for s in tr["spans"]]
        # THE acceptance shape: root + queue→prefill→ticks, in order
        assert names[0] == "request"
        assert names[1:4] == ["queue_wait", "prefill", "place"]
        assert all(n in ("decode", "verify") for n in names[4:])
        root = tr["spans"][0]
        assert root["attrs"]["n_out"] == 6
        assert "slo_ok" not in root["attrs"]       # no SLO configured
        # tick spans consistent with emitted tokens: prefill produced
        # the first token, every later token rode a decode window
        window_tokens = sum(s["attrs"]["tokens"] for s in tr["spans"][4:])
        assert window_tokens == len(b._finished[uid]) - 8 - 1 == 5
        ticks = [s["attrs"]["tick"] for s in tr["spans"][4:]]
        assert ticks == sorted(ticks)              # windows in tick order
        # spans nest in the root and the tree parents to the root span
        for s in tr["spans"][1:]:
            assert s["parent_id"] == root["span_id"]
            assert root["t0_s"] <= s["t0_s"] <= s["t1_s"] <= root["t1_s"]
        # prefill span carries the cache outcome + batch co-members
        pf = tr["spans"][2]
        assert pf["attrs"]["prefill_tokens"] == 8
        assert uid in pf["attrs"]["batch_uids"]
        # the Chrome export of this trace is valid viewer input
        doc = reqtrace.chrome_trace(tr)
        assert all(e["tid"] == uid for e in doc["traceEvents"])
        # 404 for a never-retained id
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"{ex.url}/tracez?trace_id={'0' * 32}")
        assert ei.value.code == 404
    finally:
        tracer.detach()
        ex.stop()


def test_tail_promotion_e2e_violating_request_survives_1_in_1000(eng):
    b = _batcher(eng)
    # pick a seed under which the NEXT uid is head-UNSAMPLED at 1/1000
    uid_next = b._next_uid
    seed = next(s for s in range(100)
                if not reqtrace.TraceContext.from_uid(
                    uid_next, seed=s, sample=1000).sampled)
    tracer = reqtrace.RequestTracer(sample=1000, ring=16, seed=seed)
    tracer.attach(b)
    try:
        b.set_slo(1e-4, None)          # impossible: every retire violates
        uid = b.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
        assert uid == uid_next
        _drain(b, [uid])
        [summ] = tracer.index()["retained"]
        assert summ["uid"] == uid
        assert summ["retained"] == "slo_violation"
        assert summ["slo_ok"] is False
        # and a second, SLO-met request under the same sampler is dropped
        b.set_slo(1e9, 1e9)
        uid2 = b.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
        if not reqtrace.TraceContext.from_uid(uid2, seed=seed,
                                              sample=1000).sampled:
            _drain(b, [uid2])
            assert len(tracer.index()["retained"]) == 1
    finally:
        b.set_slo(None, None)
        tracer.detach()


def test_fleet_stitch_across_two_exporters(eng):
    """The replica hop: request A retires on 'replica' A, its
    traceparent propagates with the follow-up submitted under tracer B
    (the item-2 router contract), and the fleet stitcher reads ONE
    trace spanning both /tracez endpoints."""
    b = _batcher(eng)
    ta = reqtrace.RequestTracer(sample=1, ring=16, seed=0)
    tb = reqtrace.RequestTracer(sample=1, ring=16, seed=1)
    ta.attach(b)
    uid_a = b.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    _drain(b, [uid_a])
    ta.detach()
    tr_a = next(t for t in ta.traces() if t["uid"] == uid_a)

    tb.attach(b)
    uid_b = b.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4,
                     trace_context=tr_a["traceparent"])
    _drain(b, [uid_b])
    tb.detach()
    tr_b = next(t for t in tb.traces() if t["uid"] == uid_b)
    assert tr_b["trace_id"] == tr_a["trace_id"]
    # the hop's root parents to replica A's root span
    assert tr_b["spans"][0]["parent_id"] == tr_a["spans"][0]["span_id"]

    ex_a = exporter.TelemetryExporter(port=0, tracer=ta).start()
    ex_b = exporter.TelemetryExporter(port=0, tracer=tb).start()
    try:
        view = fleet.FleetView([f"127.0.0.1:{ex_a.port}",
                                f"127.0.0.1:{ex_b.port}"])
        st = view.stitched_traces()
        merged = next(t for t in st["traces"]
                      if t["trace_id"] == tr_a["trace_id"])
        assert merged["cross_replica"] is True
        assert len(merged["replicas"]) == 2
        assert {s["uid"] for s in merged["segments"]} == {uid_a, uid_b}
        assert len(merged["spans"]) == \
            len(tr_a["spans"]) + len(tr_b["spans"])
        unix = [s["t0_unix"] for s in merged["spans"]]
        assert unix == sorted(unix)
        # the FleetServer serves the same stitched payload on /tracez
        srv = fleet.FleetServer(view, port=0).start()
        try:
            via_http = _get(f"{srv.url}/tracez")
            assert via_http["n_cross_replica"] >= 1
        finally:
            srv.stop()
        # the fleet rollup reads the new queue-wait histogram
        view.scrape_once()
        fz = view.fleetz()
        assert fz["fleet"]["queue_wait_p99_ms"] is not None
    finally:
        ex_a.stop()
        ex_b.stop()


def test_queue_wait_histogram_moves_on_admission(eng):
    h = registry.get_registry().histogram(
        "serving_queue_wait_ms", buckets=registry.MS_BUCKETS)
    child = h._default_child()
    count0 = child.count
    b = _batcher(eng)
    b.run([np.arange(1, 9, dtype=np.int32)], max_new_tokens=3, ticks=2)
    assert child.count == count0 + 1
    assert child.sum >= 0


def test_flight_dump_embeds_retained_index_and_pretty_renders(eng, tmp_path):
    b = _batcher(eng)
    rec = flightrec.maybe_install(str(tmp_path))
    try:
        tracer = reqtrace.install(b, sample=1000, ring=16, seed=0)
        # force a violating retirement so a promoted trace exists
        b.set_slo(1e-4, None)
        uid = b.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
        _drain(b, [uid])
        path = flightrec.dump("test:reqtrace")
        assert path is not None
        with open(path) as fh:
            payload = json.load(fh)
        idx = payload["reqtrace"]
        assert any(s["retained"] == "slo_violation" and s["uid"] == uid
                   for s in idx["retained"])
        text = flightrec.pretty(path)
        assert "retained SLO-violating traces" in text
        assert f"uid={uid}" in text
    finally:
        b.set_slo(None, None)
        reqtrace.uninstall()
        flightrec.disarm()


def test_reqtrace_off_by_default_no_observers(eng):
    """The zero-cost contract: without DSTPU_REQTRACE no observer is
    registered, so the serving loop's _note_lifecycle short-circuits."""
    b = _batcher(eng)
    assert b._lifecycle_observers == []
    b.run([np.arange(1, 9, dtype=np.int32)], max_new_tokens=2, ticks=2)
    assert b._lifecycle_observers == []
