"""Trace replay against a real ContinuousBatcher (telemetry/loadgen.py):
the per-request lifecycle waterfall, retire-time SLO tagging, /statusz
tail-percentile agreement, SLO calibration, and the end-to-end
regression gate.  z-sorted: batcher compiles run late in the tier-1
alphabetical window (the test_zspecdec convention)."""
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.inference.serving import ContinuousBatcher
from deepspeed_tpu.telemetry import loadgen

from .simple_model import tiny_gpt2_engine

MAX_TOKENS = 48


@pytest.fixture(scope="module")
def eng():
    mesh_mod.set_mesh(None)
    yield tiny_gpt2_engine(max_tokens=MAX_TOKENS)
    mesh_mod.set_mesh(None)


def _batcher(eng, **kw):
    return ContinuousBatcher(eng, n_slots=2, seed=0, **kw)


def _trace(**kw):
    base = dict(seed=5, n_requests=6, rate_rps=200.0,
                prompt_len_mix=((6, 0.5), (10, 0.5)),
                gen_len_min=2, gen_len_max=6, vocab_size=256,
                max_total_len=MAX_TOKENS)
    base.update(kw)
    return loadgen.generate_trace(loadgen.TraceConfig(**base))


LOOSE = loadgen.SLOConfig(ttft_ms=1e9, tpot_ms=1e9)


def test_replay_end_to_end_waterfalls(eng):
    b = _batcher(eng)
    trace = _trace()
    report = loadgen.replay(b, trace, LOOSE, ticks=2, time_scale=100.0)
    assert report.offered == 6 and report.completed == 6
    assert report.goodput["slo_attainment"] == 1.0
    assert report.goodput["total_output_tokens"] == \
        trace.total_max_new_tokens        # no EOS id → runs to budget
    assert report.queue_timeline
    by_idx = {w["idx"]: w for w in report.waterfalls}
    for r in trace.requests:
        w = by_idx[r.idx]
        # full lifecycle: submit → prefill_start → first_token → retire,
        # monotonically ordered, with the emitted-token split
        ts = [w["t_submit_s"], w["t_prefill_start_s"],
              w["t_first_token_s"], w["t_retire_s"]]
        assert all(t is not None for t in ts)
        assert ts == sorted(ts)
        assert w["n_out"] == r.max_new_tokens
        # first token comes from prefill; the rest from decode ticks
        assert w["decode_tokens"] == r.max_new_tokens - 1
        assert w["ttft_ms"] is not None and w["slo_ok"] is True
        # coordinated-omission guard: report TTFT is anchored on the
        # TRACE arrival, so it is >= the batcher's submit-based stamp
        assert w["submit_lag_ms"] >= 0
        assert w["ttft_ms"] >= w["ttft_submit_ms"] - 1.0
        for phase in ("queued_s", "prefill_s", "decode_s"):
            assert w[phase] is not None and w[phase] >= 0
    # renderers survive real data
    assert "goodput (under SLO)" in report.table()
    assert "ttft_ms" in report.format_waterfalls()


def test_replay_token_deterministic_across_runs(eng):
    trace = _trace(seed=11)
    totals = []
    for _ in range(2):
        rep = loadgen.replay(_batcher(eng), trace, LOOSE, ticks=2,
                             time_scale=100.0)
        totals.append(rep.goodput["total_output_tokens"])
        assert rep.completed == rep.offered
    assert totals[0] == totals[1]


def test_retire_time_slo_tagging_and_statusz(eng):
    b = _batcher(eng)
    prompt = np.arange(1, 9, dtype=np.int32)
    # impossible bound: every retirement is a TTFT violation
    b.set_slo(1e-4, None)
    b.run([prompt], max_new_tokens=4, ticks=2)
    st = b._telemetry_status()
    assert st["slo"]["violated"] == 1 and st["slo"]["met"] == 0
    # loose bound: met
    b.set_slo(1e9, 1e9)
    b.run([prompt], max_new_tokens=4, ticks=2)
    st = b._telemetry_status()
    assert st["slo"]["met"] == 1
    # tail percentiles from the same windows the load report reads
    assert st["ttft_p99_ms"] > 0
    assert st["tpot_p99_ms"] >= st["tpot_p50_ms"] > 0
    stats = b.latency_stats()
    assert stats["ttft_p99_s"] >= stats["ttft_p50_s"]
    assert stats["tpot_p99_ms"] == pytest.approx(st["tpot_p99_ms"],
                                                 abs=1e-3)
    # clearing disables tagging (the statusz section disappears; the
    # per-instance tallies stop moving)
    b.set_slo(None, None)
    b.run([prompt], max_new_tokens=2, ticks=2)
    assert b._telemetry_status()["slo"] is None
    assert b._slo_met_n == 1


def test_lifecycle_observer_remove_and_error_isolation(eng):
    b = _batcher(eng)
    seen = []

    def bad_observer(t, uid, event, extra):
        raise RuntimeError("observer bug")

    remove_bad = b.add_lifecycle_observer(bad_observer)
    remove_ok = b.add_lifecycle_observer(
        lambda t, uid, event, extra: seen.append(event))
    # a broken observer must never break serving
    b.run([np.arange(1, 7, dtype=np.int32)], max_new_tokens=3, ticks=2)
    assert {"submit", "prefill_start", "first_token", "retire"} <= set(seen)
    # retire is the LAST event for a uid (pending emits flush first) —
    # observers may finalize a request's record at retire
    assert seen[-1] == "retire"
    remove_bad()
    remove_ok()
    n = len(seen)
    b.run([np.arange(1, 7, dtype=np.int32)], max_new_tokens=2, ticks=2)
    assert len(seen) == n            # removed observers stay removed


def test_serving_spans_carry_uids(eng):
    from deepspeed_tpu.telemetry import trace as trace_mod

    class Spy:
        spans = []

        def span_enter(self, name):
            pass

        def span_exit(self, name, dur_s, args):
            self.spans.append((name, args))

    spy = Spy()
    trace_mod.add_span_observer(spy)
    try:
        b = _batcher(eng)
        uid = b.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
        while uid not in b._finished:
            b.step(ticks=2)
    finally:
        trace_mod.remove_span_observer(spy)
    prefills = [a for n, a in spy.spans
                if n == "serve/prefill" and (a or {}).get("uids")]
    decodes = [a for n, a in spy.spans
               if n == "serve/decode-tick" and (a or {}).get("uids")]
    assert any(uid in a["uids"] for a in prefills)
    assert any(uid in a["uids"] for a in decodes)


def test_calibrate_slo_returns_positive_bounds(eng):
    b = _batcher(eng)
    b.run([np.arange(1, 9, dtype=np.int32)], max_new_tokens=4, ticks=2)
    slo = loadgen.calibrate_slo(b, prompt_len=8, max_new=4, runs=2)
    assert slo.ttft_ms > 0 and slo.tpot_ms > 0


def test_gate_end_to_end_pass_and_fail(eng):
    trace = _trace(seed=21)
    rep = loadgen.replay(_batcher(eng), trace, LOOSE, ticks=2,
                         time_scale=100.0).to_jsonable()
    baseline = {
        "trace_sha256": rep["trace_sha256"],
        "total_output_tokens": rep["goodput"]["total_output_tokens"],
        "slo_attainment_min": 0.8, "goodput_token_ratio_min": 0.8,
        "tolerance": 0.1,
    }
    ok, _ = loadgen.check_baseline(rep, baseline)
    assert ok
    # a goodput drop beyond tolerance fails the gate
    baseline["slo_attainment_min"] = 2.0
    ok, msgs = loadgen.check_baseline(rep, baseline)
    assert not ok and any("regression" in m for m in msgs)


def test_statusz_loadgen_section_after_replay(eng):
    from deepspeed_tpu.telemetry import loadgen as lg

    loadgen.replay(_batcher(eng), _trace(seed=31, n_requests=3), LOOSE,
                   ticks=2, time_scale=100.0)
    st = lg._loadgen_status()
    assert st is not None
    assert st["offered"] == 3 and st["completed"] == 3
    assert st["slo_attainment"] == 1.0
