"""Admission-control + chaos host-side units (inference/admission.py,
testing/chaos.py): queue-bound and deadline-estimate shedding, priority
ordering, degradation-ladder transitions (flap suppression, reverse
unwind), the resolve surface, and chaos-site determinism from a seed.

Everything here is host bookkeeping — submits, sweeps, and scripted
ladder evaluations, no decode steps — so the file stays in the fast
half of the tier-1 alphabetical window.  Device-side behavior (shed
lifecycle + metrics e2e, deadline retirement freeing pages, chaos
replay completing a trace, drain leak-freedom) lives in
``test_zadmission.py``."""
import time

import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.inference import admission
from deepspeed_tpu.inference.serving import ContinuousBatcher
from deepspeed_tpu.testing import chaos

from .simple_model import tiny_gpt2_engine

VOCAB = 64


def _make_engine(**kwargs):
    return tiny_gpt2_engine(max_tokens=64, **kwargs)


@pytest.fixture(scope="module")
def eng():
    mesh_mod.set_mesh(None)
    engine = _make_engine()
    yield engine
    mesh_mod.set_mesh(None)


def _prompt(rng, n=8):
    return rng.integers(0, VOCAB, size=(n,)).astype(np.int32)


# -- resolve surface --------------------------------------------------------

def test_resolve_off_by_default(eng, monkeypatch):
    monkeypatch.delenv(admission.ADMISSION_ENV, raising=False)
    assert admission.resolve_admission(eng, None) is None


def test_resolve_env_enables_and_kills(eng, monkeypatch):
    monkeypatch.setenv(admission.ADMISSION_ENV, "1")
    assert admission.resolve_admission(eng, None) is not None
    # env 0 kills even a READY instance (the kvreuse convention)
    monkeypatch.setenv(admission.ADMISSION_ENV, "0")
    ready = admission.AdmissionController()
    assert admission.resolve_admission(eng, ready) is None


def test_resolve_explicit_beats_env(eng, monkeypatch):
    monkeypatch.setenv(admission.ADMISSION_ENV, "1")
    assert admission.resolve_admission(eng, False) is None
    monkeypatch.delenv(admission.ADMISSION_ENV, raising=False)
    # {} enables defaults; a dict carries policy kwargs; a ready
    # instance passes through
    c = admission.resolve_admission(eng, {})
    assert c is not None and c.policy.max_queue_depth == 64
    c = admission.resolve_admission(eng, {"max_queue_depth": 3})
    assert c.policy.max_queue_depth == 3
    ready = admission.AdmissionController()
    assert admission.resolve_admission(eng, ready) is ready
    # a bad policy dict warns and disables, never raises
    assert admission.resolve_admission(eng, {"no_such_knob": 1}) is None


# -- estimator --------------------------------------------------------------

def test_estimator_learns_then_estimates():
    est = admission._Estimator(alpha=0.5)
    assert est.estimate_ttft_ms(4) is None          # nothing learned
    est.note_prefill(10.0)
    assert est.estimate_ttft_ms(4) is None          # wait term missing
    est.note_wait(40.0, depth_at_submit=4)          # 10 ms per queued
    assert est.estimate_ttft_ms(0) == pytest.approx(10.0)
    assert est.estimate_ttft_ms(4) == pytest.approx(50.0)
    # EWMA, not last-wins
    est.note_prefill(30.0)
    assert est.estimate_ttft_ms(0) == pytest.approx(20.0)
    # depth 0 observations still count (clamped divisor)
    est.note_wait(5.0, depth_at_submit=0)
    assert est.wait_per_depth_ms == pytest.approx(7.5)


def test_check_submit_deadline_estimate_shedding():
    c = admission.AdmissionController(
        admission.AdmissionPolicy(deadline_ms=100.0))
    c._est_min_depth = 2
    c.est.note_prefill(20.0)
    c.est.note_wait(30.0, depth_at_submit=1)        # 30 ms per queued
    # below the min depth: never estimate-shed (idle capacity — and
    # admissions keep the estimator fresh; shedding here is the
    # death-spiral case)
    assert c.check_submit(depth=1, priority=0, deadline_ms=None) is None
    # 20 + 4*30 = 140 > 100 → shed
    assert c.check_submit(depth=4, priority=0, deadline_ms=None) \
        == "deadline_unmeetable"
    # a generous per-request deadline overrides the policy default
    assert c.check_submit(depth=4, priority=0, deadline_ms=500.0) is None
    # the batcher's SLO TTFT bound sheds too
    c2 = admission.AdmissionController()
    c2._est_min_depth = 1
    c2.est.note_prefill(20.0)
    c2.est.note_wait(30.0, depth_at_submit=1)
    assert c2.check_submit(depth=4, priority=0, deadline_ms=None,
                           slo_ttft_ms=100.0) == "deadline_unmeetable"
    # no bounds at all → never sheds on the estimate
    assert c2.check_submit(depth=64, priority=0, deadline_ms=None) is None


# -- degradation ladder -----------------------------------------------------

def _ladder_controller(hold=1.0, recover=2.0):
    return admission.AdmissionController(
        admission.AdmissionPolicy(ladder_hold_s=hold,
                                  ladder_recover_s=recover))


def test_ladder_escalates_and_unwinds_in_reverse():
    c = _ladder_controller()
    c._on_alert({"rule": "slo_burn", "state": "firing"})
    # _on_alert evaluates with real monotonic time; drive the rest with
    # scripted clocks
    assert c.stage >= 1
    t0 = c._last_move
    c._evaluate_ladder(t0 + 0.5)                  # inside the hold
    assert c.stage == 1
    c._evaluate_ladder(t0 + 1.1)
    assert c.stage == 2
    assert not c.allow_specdec() or c.stage < 3
    assert c.cap_max_new(500) == c.policy.degraded_max_new_tokens
    c._evaluate_ladder(c._last_move + 1.1)
    assert c.stage == 3 and not c.allow_specdec()
    c._evaluate_ladder(c._last_move + 10.0)       # capped at the top
    assert c.stage == 3
    # recovery: reverse unwind, one stage per sustained clear interval
    c._on_alert({"rule": "slo_burn", "state": "cleared"})
    base = max(c._last_move, c._all_clear_since)
    c._evaluate_ladder(base + 1.0)                # not sustained yet
    assert c.stage == 3
    c._evaluate_ladder(base + 2.1)
    assert c.stage == 2
    c._evaluate_ladder(c._last_move + 2.1)
    assert c.stage == 1
    c._evaluate_ladder(c._last_move + 2.1)
    assert c.stage == 0 and c.allow_specdec()
    assert c.cap_max_new(500) == 500
    up = [t for t in c._transitions if t["direction"] == "up"]
    down = [t for t in c._transitions if t["direction"] == "down"]
    assert len(up) == 3 and len(down) == 3


def test_ladder_flap_suppression():
    c = _ladder_controller(hold=1.0, recover=5.0)
    c._on_alert({"rule": "queue_runaway", "state": "firing"})
    assert c.stage == 1
    t0 = c._last_move
    # flapping clear/fire: the clear resets the all-clear clock, so a
    # short clear window never unwinds
    c._on_alert({"rule": "queue_runaway", "state": "cleared"})
    c._evaluate_ladder(t0 + 2.0)                  # clear, but < recover
    assert c.stage == 1
    c._on_alert({"rule": "queue_runaway", "state": "firing"})
    assert c._all_clear_since is None
    c._on_alert({"rule": "queue_runaway", "state": "cleared"})
    # the all-clear clock restarted: still not sustained
    c._evaluate_ladder(c._all_clear_since + 4.9)
    assert c.stage == 1
    c._evaluate_ladder(c._all_clear_since + 5.1)
    assert c.stage == 0


def test_ladder_ignores_non_overload_rules():
    c = _ladder_controller()
    c._on_alert({"rule": "recompile_storm", "state": "firing"})
    c._on_alert({"rule": "goodput_drop", "state": "firing"})
    assert c.stage == 0 and not c._firing


def test_shed_class_at_stage_one():
    c = _ladder_controller()
    assert c.check_submit(depth=0, priority=5, deadline_ms=None) is None
    c.stage = 1
    assert c.check_submit(depth=0, priority=1, deadline_ms=None) \
        == "shed_class"
    assert c.check_submit(depth=0, priority=0, deadline_ms=None) is None


# -- batcher integration (host-only: no decode steps) -----------------------

def test_queue_bound_sheds_and_evicts_by_priority(eng):
    rng = np.random.default_rng(0)
    b = ContinuousBatcher(eng, n_slots=2,
                          admission={"max_queue_depth": 2})
    u0 = b.submit(_prompt(rng), max_new_tokens=4, priority=1)
    u1 = b.submit(_prompt(rng), max_new_tokens=4, priority=1)
    # queue full, equal priority → the arrival sheds
    u2 = b.submit(_prompt(rng), max_new_tokens=4, priority=1)
    assert b.rejected[u2] == "queue_full"
    assert u0 not in b.rejected and u1 not in b.rejected
    # queue full, HIGHER-priority arrival → the lowest-priority queued
    # request is evicted instead
    u3 = b.submit(_prompt(rng), max_new_tokens=4, priority=0)
    assert u3 not in b.rejected
    assert b.rejected[u0] == "queue_full"        # FIFO victim among p=1
    # priority ordering: the p=0 arrival queues AHEAD of the p=1 one
    assert [r.uid for r in b._queue] == [u3, u1]


def test_priority_insertion_is_stable_fifo_within_class(eng):
    rng = np.random.default_rng(1)
    b = ContinuousBatcher(eng, n_slots=2, admission={})
    uids = [b.submit(_prompt(rng), max_new_tokens=4, priority=p)
            for p in (2, 0, 1, 0, 2, 1)]
    got = [r.uid for r in b._queue]
    assert got == [uids[1], uids[3], uids[2], uids[5], uids[0], uids[4]]


def test_deadline_sweep_sheds_expired_queued(eng):
    rng = np.random.default_rng(2)
    b = ContinuousBatcher(eng, n_slots=2, admission={})
    uid = b.submit(_prompt(rng), max_new_tokens=4, deadline_ms=1.0)
    ok = b.submit(_prompt(rng), max_new_tokens=4, deadline_ms=60_000.0)
    assert uid in b.admission.deadlines
    time.sleep(0.01)
    b._deadline_sweep()
    assert b.rejected[uid] == "deadline_expired"
    assert uid not in b.admission.deadlines
    assert ok not in b.rejected
    assert [r.uid for r in b._queue] == [ok]


def test_wait_guards_instead_of_spinning(eng):
    rng = np.random.default_rng(3)
    b = ContinuousBatcher(eng, n_slots=2, admission={"max_queue_depth": 1})
    # an unknown uid can never finish: immediate error, no busy-spin
    with pytest.raises(RuntimeError):
        b.wait([12345])
    assert b.wait([12345], partial=True) == {}
    u0 = b.submit(_prompt(rng), max_new_tokens=4)
    u1 = b.submit(_prompt(rng), max_new_tokens=4)   # shed (bound = 1)
    assert u1 in b.rejected
    # a shed uid is TERMINAL, not an error — wait returns without it
    assert b.wait([u1]) == {}
    # max_ticks exhaustion raises instead of looping forever
    with pytest.raises(TimeoutError):
        b.wait([u0], max_ticks=0)
    with pytest.raises(TimeoutError):
        b.wait([u0], timeout_s=0.0)
    assert b.wait([u0, u1], max_ticks=0, partial=True) == {}


def test_submit_during_drain_sheds(eng):
    rng = np.random.default_rng(4)
    b = ContinuousBatcher(eng, n_slots=2, admission={})
    summary = b.drain(timeout_s=0.5, flush=False)
    assert summary["leaked_slots"] == 0 and summary["forced"] == 0
    uid = b.submit(_prompt(rng), max_new_tokens=4)
    assert b.rejected[uid] == "draining"
    assert b.pending == 0


def test_rejected_lifecycle_event_and_metrics(eng):
    rng = np.random.default_rng(5)
    b = ContinuousBatcher(eng, n_slots=2, admission={"max_queue_depth": 1})
    events = []
    b.add_lifecycle_observer(
        lambda t, uid, ev, extra: events.append((uid, ev, extra)))
    b.submit(_prompt(rng), max_new_tokens=4)
    u = b.submit(_prompt(rng), max_new_tokens=4)
    rej = [(uid, ev, ex) for uid, ev, ex in events if ev == "rejected"]
    assert rej == [(u, "rejected", {"reason": "queue_full", "queued": 1})]
    st = b.admission._telemetry_status()
    assert st["rejected"] == {"queue_full": 1}
    assert st["stage"] == "normal"


# -- chaos plan/engine ------------------------------------------------------

def test_fault_spec_validation():
    with pytest.raises(ValueError):
        chaos.FaultSpec(site="no_such_site", at=(0,))
    with pytest.raises(ValueError):
        chaos.FaultSpec(site="slow_tick")           # can never fire
    with pytest.raises(ValueError):
        chaos.FaultSpec(site="slow_tick", every=0)


def test_plan_json_round_trip():
    plan = chaos.ChaosPlan(seed=3, faults=(
        chaos.FaultSpec(site="prefill_failure", at=(1, 4), count=2),
        chaos.FaultSpec(site="slow_tick", every=3, arg=0.25),
        chaos.FaultSpec(site="drafter_exception", p=0.5, count=1),
    ))
    back = chaos.ChaosPlan.from_json(
        __import__("json").dumps(plan.to_jsonable()))
    assert back == plan
    assert back.planned_sites() == ["drafter_exception",
                                    "prefill_failure", "slow_tick"]


def test_chaos_at_every_count_semantics():
    eng_ = chaos.ChaosEngine(chaos.ChaosPlan(seed=0, faults=(
        chaos.FaultSpec(site="prefill_failure", at=(1, 3)),
        chaos.FaultSpec(site="slow_tick", every=2, count=2),
    )))
    hits = [eng_.fire("prefill_failure") is not None for _ in range(5)]
    assert hits == [False, True, False, True, False]
    # every=2 = each 2nd invocation (1-based): fires at invocations
    # 1 and 3, then the count cap stops it — never at 0
    hits = [eng_.fire("slow_tick") is not None for _ in range(6)]
    assert hits == [False, True, False, True, False, False]
    assert eng_.all_planned_fired()
    s = eng_.summary()
    assert s["fired"] == {"prefill_failure": 2, "slow_tick": 2}
    chaos.assert_plan_fired(eng_, expected=[
        ("prefill_failure", 1), ("prefill_failure", 3),
        ("slow_tick", 1), ("slow_tick", 3)])
    with pytest.raises(AssertionError):
        chaos.assert_plan_fired(eng_, expected=[("slow_tick", 1)])


def test_chaos_p_trigger_is_seed_deterministic():
    def fires(seed):
        e = chaos.ChaosEngine(chaos.ChaosPlan(seed=seed, faults=(
            chaos.FaultSpec(site="drafter_exception", p=0.3),)))
        return [e.fire("drafter_exception") is not None
                for _ in range(40)]

    a, b = fires(11), fires(11)
    assert a == b and any(a) and not all(a)
    assert fires(12) != a


def test_maybe_fire_without_plan_is_none():
    chaos.clear()
    assert chaos.get_engine() is None
    assert chaos.maybe_fire("slow_tick") is None
    eng_ = chaos.install_plan(chaos.ChaosPlan(seed=0, faults=(
        chaos.FaultSpec(site="slow_tick", at=(0,)),)))
    try:
        assert chaos.maybe_fire("slow_tick") is not None
        assert eng_.summary()["fired"] == {"slow_tick": 1}
    finally:
        chaos.clear()
    assert chaos.maybe_fire("slow_tick") is None


def test_chaos_env_install(tmp_path, monkeypatch):
    chaos.clear()
    plan_path = tmp_path / "plan.json"
    plan_path.write_text(__import__("json").dumps(
        {"seed": 5, "faults": [{"site": "slow_tick", "at": [0],
                                "arg": 0.01}]}))
    monkeypatch.setenv(chaos.CHAOS_PLAN_ENV, str(plan_path))
    try:
        eng_ = chaos.maybe_install_env()
        assert eng_ is not None and eng_.plan.seed == 5
        # idempotent: a second resolve returns the SAME engine (site
        # counters keep counting from the first install)
        assert chaos.maybe_install_env() is eng_
    finally:
        chaos.clear()
    monkeypatch.setenv(chaos.CHAOS_PLAN_ENV, str(tmp_path / "nope.json"))
    assert chaos.maybe_install_env() is None    # bad path warns, no raise
    chaos.clear()
