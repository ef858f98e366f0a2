"""Fast host units for the device physics table
(profiling/flops_profiler.py) and the anomaly detectors
(telemetry/anomaly.py).

Everything here is hand-built series / tiny-jit work — no models, no
mesh — so the file stays cheap inside the tier-1 window.  The serving
e2e (induced alert storms on a CPU-mesh run) lives z-sorted in
``test_zattribution.py``.
"""
import time

import numpy as np
import pytest

from deepspeed_tpu.profiling import flops_profiler
from deepspeed_tpu.telemetry import anomaly
from deepspeed_tpu.telemetry import registry as telemetry_registry
from deepspeed_tpu.telemetry.anomaly import (
    AcceptanceCollapseDetector, AnomalyEngine, Detector,
    GoodputDropDetector, QueueRunawayDetector, RecompileStormDetector,
    Series, SloBurnDetector)


# ----------------------------------------------------------------------
# device physics: one table, in profiling/flops_profiler.py
# ----------------------------------------------------------------------
def test_device_tables_shared_and_chips_only():
    # bench.py and the autotuner read THESE tables (both import the
    # module); they carry chips only
    import jax

    from deepspeed_tpu.autotuning import autotuner

    assert autotuner.flops_profiler is flops_profiler
    for table in (flops_profiler.PEAK_FLOPS, flops_profiler.HBM_BYTES_S,
                  flops_profiler.HBM_BYTES):
        assert "cpu" not in table
        assert set(table) == set(flops_profiler.PEAK_FLOPS)
    assert not flops_profiler.device_known(jax.devices()[0])


@pytest.mark.parametrize("lookup", ["device_peak_flops",
                                    "device_hbm_bytes_s",
                                    "device_hbm_bytes"])
def test_device_not_in_the_tables_is_an_error(lookup):
    import jax

    lookup = getattr(flops_profiler, lookup)
    with pytest.raises(ValueError, match="device_kind"):
        lookup(jax.devices()[0])
    with pytest.raises(ValueError, match="device_kind"):
        lookup()            # device 0 of the CPU mesh


def test_decode_stream_floor_hand_math(nominal_cpu_physics):
    params = {"w": np.zeros((10, 10), np.float32)}        # 400 B
    slot_cache = {"k": np.zeros((4, 8), np.float32)}      # 128 B
    d = flops_profiler.decode_stream_floor(params, slot_cache, n_slots=2,
                                        dev=None)
    assert d["weight_stream_bytes"] == 400
    assert d["kv_stream_bytes_per_tick"] == 256
    assert d["bw_floor_ms_per_tick"] == pytest.approx(
        1000.0 * (400 + 256) / d["hbm_bytes_s"])


def test_harvest_costs_real_compiled():
    import jax
    import jax.numpy as jnp

    c = jax.jit(lambda x: x @ x).lower(jnp.ones((32, 32))).compile()
    costs = flops_profiler.harvest_costs(c)
    assert costs is not None
    assert costs["flops"] > 0
    assert costs["bytes_accessed"] > 0


# ----------------------------------------------------------------------
# series
# ----------------------------------------------------------------------
def test_series_delta_window():
    s = Series()
    for t, v in [(0, 0), (10, 5), (20, 9), (30, 12)]:
        s.add(t, v)
    assert s.delta(15, now=30) == pytest.approx(3)     # 12 - 9
    assert s.delta(100, now=30) == pytest.approx(12)   # 12 - 0
    assert Series().delta(10) is None
    s1 = Series()
    s1.add(0, 1)
    assert s1.delta(10, now=0) is None                 # one sample


def test_series_increasing_run():
    s = Series()
    for t, v in enumerate([1, 2, 3, 4]):
        s.add(t, v)
    assert s.increasing_run(3)
    s.add(4, 4)          # plateau breaks strictness
    assert not s.increasing_run(3)
    assert not Series().increasing_run(1)


# ----------------------------------------------------------------------
# detector hysteresis
# ----------------------------------------------------------------------
class _Scripted(Detector):
    """check() replays a scripted list of violations/None."""

    name = "scripted"

    def __init__(self, script, fire_after=1, clear_after=3):
        super().__init__()
        self.fire_after = fire_after
        self.clear_after = clear_after
        self._script = list(script)

    def check(self, engine, now):
        return self._script.pop(0) if self._script else None


class _NoSampleEngine(AnomalyEngine):
    """Evaluation-only engine: series are hand-built by the test."""

    def _sample(self, now):
        pass


def _drain(det, engine, evals):
    out = []
    for i in range(evals):
        out.extend(det.step(engine, float(i)))
    return out


def test_hysteresis_fire_after_and_clear_after():
    bad = {"value": 1.0, "threshold": 0.5}
    det = _Scripted([bad, bad, bad, None, None, None, None],
                    fire_after=2, clear_after=3)
    eng = _NoSampleEngine(detectors=[])
    evs = _drain(det, eng, 7)
    # fires on the 2nd bad eval, clears on the 3rd good one — exactly
    # one transition each; the 3rd bad eval emits nothing
    assert [(e["state"]) for e in evs] == ["firing", "cleared"]
    assert evs[0]["t"] == 1.0 and evs[1]["t"] == 5.0


def test_hysteresis_flap_suppression():
    bad = {"value": 1.0, "threshold": 0.5}
    # bad/good alternation with clear_after=3 never clears (and never
    # re-fires): one firing event total
    det = _Scripted([bad, None, bad, None, bad, None], fire_after=1,
                    clear_after=3)
    eng = _NoSampleEngine(detectors=[])
    evs = _drain(det, eng, 6)
    assert [e["state"] for e in evs] == ["firing"]
    assert det.firing


def test_recompile_storm_fires_exactly_once():
    det = RecompileStormDetector(n=3, window_s=60)
    eng = _NoSampleEngine(detectors=[det])
    eng.series["recompiles"].add(0.0, 0.0)
    eng.series["recompiles"].add(10.0, 5.0)        # 5 recompiles in 10 s
    evs = eng.observe(now=10.0, force=True)
    evs += eng.observe(now=11.0, force=True)       # still storming
    fires = [e for e in evs if e["state"] == "firing"]
    assert len(fires) == 1
    assert fires[0]["rule"] == "recompile_storm"
    assert fires[0]["value"] == pytest.approx(5.0)
    assert eng.active().get("recompile_storm") is not None


def test_recompile_storm_clears_when_window_quiets():
    det = RecompileStormDetector(n=3, window_s=20)
    eng = _NoSampleEngine(detectors=[det])
    eng.series["recompiles"].add(0.0, 0.0)
    eng.series["recompiles"].add(5.0, 5.0)
    eng.observe(now=5.0, force=True)
    assert det.firing
    # the storm samples age out of the window; flat counter since
    for t in (30.0, 31.0, 32.0):
        eng.series["recompiles"].add(t, 5.0)
        eng.observe(now=t, force=True)
    assert not det.firing
    assert eng.active() == {}


def test_burn_rate_fixture_math():
    # hand-computed: 6 met + 2 violations = 0.25 burn over 8 events
    rate, events = SloBurnDetector.burn_rate(6.0, 2.0)
    assert rate == pytest.approx(0.25)
    assert events == 8.0
    assert SloBurnDetector.burn_rate(None, 2.0) is None
    assert SloBurnDetector.burn_rate(0.0, 0.0) == (0.0, 0.0)


def test_slo_burn_respects_min_events():
    det = SloBurnDetector(burn=0.5, window_s=60, min_events=8)
    eng = _NoSampleEngine(detectors=[det])
    # 3 retirements, all violations: 100% burn but below min_events
    eng.series["slo_met"].add(0.0, 0.0)
    eng.series["slo_met"].add(10.0, 0.0)
    eng.series["slo_violations"].add(0.0, 0.0)
    eng.series["slo_violations"].add(10.0, 3.0)
    assert eng.observe(now=10.0, force=True) == []
    # 10 retirements, 6 violations: 60% burn over enough events
    eng.series["slo_met"].add(20.0, 4.0)
    eng.series["slo_violations"].add(20.0, 6.0)
    evs = eng.observe(now=20.0, force=True)
    assert [e["rule"] for e in evs] == ["slo_burn"]
    assert evs[0]["value"] == pytest.approx(0.6)


def test_queue_runaway_needs_run_and_floor():
    det = QueueRunawayDetector(run=3, min_depth=10)
    eng = _NoSampleEngine(detectors=[det])
    for t, v in enumerate([1, 2, 3, 4]):       # increasing but shallow
        eng.series["queue_depth"].add(float(t), float(v))
    assert eng.observe(now=3.0, force=True) == []
    for t, v in enumerate([11, 14, 18, 25], start=4):
        eng.series["queue_depth"].add(float(t), float(v))
    evs = eng.observe(now=7.0, force=True)
    assert [e["rule"] for e in evs] == ["queue_runaway"]


def test_acceptance_collapse_requires_moving_verify_ticks():
    det = AcceptanceCollapseDetector(min_rate=0.2, window_s=60)
    det.fire_after = 1
    eng = _NoSampleEngine(detectors=[det])
    eng.series["acceptance_rate"].add(0.0, 0.05)
    # no verify ticks moving: speculation is idle, not collapsing
    assert eng.observe(now=0.0, force=True) == []
    eng.series["verify_ticks"].add(0.0, 0.0)
    eng.series["verify_ticks"].add(10.0, 12.0)
    eng.series["acceptance_rate"].add(10.0, 0.05)
    evs = eng.observe(now=10.0, force=True)
    assert [e["rule"] for e in evs] == ["acceptance_collapse"]


def test_goodput_drop_waits_for_warmup():
    det = GoodputDropDetector(min_ratio=0.5, min_wall_s=100)
    det.fire_after = 1
    eng = _NoSampleEngine(detectors=[det])
    eng.series["goodput_ratio"].add(0.0, 0.1)
    eng.series["goodput_wall"].add(0.0, 10.0)      # still warming up
    assert eng.observe(now=0.0, force=True) == []
    eng.series["goodput_ratio"].add(1.0, 0.1)
    eng.series["goodput_wall"].add(1.0, 200.0)
    evs = eng.observe(now=1.0, force=True)
    assert [e["rule"] for e in evs] == ["goodput_drop"]


# ----------------------------------------------------------------------
# engine dispatch: metrics, ring, subscribers
# ----------------------------------------------------------------------
def test_dispatch_counters_gauge_ring_and_subscribers():
    det = RecompileStormDetector(n=2, window_s=60)
    det.clear_after = 1
    eng = _NoSampleEngine(detectors=[det])
    reg = telemetry_registry.get_registry()
    c0 = reg.counter("alerts_total", labelnames=("rule",)).labels(
        rule="recompile_storm").value
    got = []
    remove = eng.subscribe(got.append)
    eng.series["recompiles"].add(0.0, 0.0)
    eng.series["recompiles"].add(1.0, 4.0)
    eng.observe(now=1.0, force=True)
    assert reg.counter("alerts_total", labelnames=("rule",)).labels(
        rule="recompile_storm").value == c0 + 1
    assert reg.gauge("alerts_firing", labelnames=("rule",)).labels(
        rule="recompile_storm").value == 1.0
    assert [e["state"] for e in got] == ["firing"]
    # quiet window → cleared; unsubscribed callback sees nothing more
    remove()
    for t in (100.0, 101.0):
        eng.series["recompiles"].add(t, 4.0)
        eng.observe(now=t, force=True)
    assert reg.gauge("alerts_firing", labelnames=("rule",)).labels(
        rule="recompile_storm").value == 0.0
    assert len(got) == 1
    states = [e["state"] for e in eng.recent()]
    assert states == ["firing", "cleared"]
    st = eng.status()
    assert "recompile_storm" in st["rules"]
    assert st["rules"]["recompile_storm"]["n"] == 2


def test_broken_subscriber_and_detector_isolated():
    class _Boom(Detector):
        name = "boom"

        def check(self, engine, now):
            raise RuntimeError("detector bug")

    det = RecompileStormDetector(n=1, window_s=60)
    eng = _NoSampleEngine(detectors=[_Boom(), det])
    eng.subscribe(lambda ev: 1 / 0)
    eng.series["recompiles"].add(0.0, 0.0)
    eng.series["recompiles"].add(1.0, 3.0)
    evs = eng.observe(now=1.0, force=True)   # neither failure propagates
    assert [e["rule"] for e in evs] == ["recompile_storm"]


def test_observe_throttle_and_real_sample_smoke():
    eng = AnomalyEngine()        # the REAL sampler against the registry
    evs = eng.observe(force=True)
    assert isinstance(evs, list)
    # throttled second call (within 1 s) is a no-op
    assert eng.observe() == []
    assert len(eng.series["recompiles"]) >= 1


def test_env_knob_overrides(monkeypatch):
    monkeypatch.setenv("DSTPU_ALERT_RECOMPILE_N", "7")
    monkeypatch.setenv("DSTPU_ALERT_SLO_BURN", "0.9")
    assert RecompileStormDetector().n == 7
    assert SloBurnDetector().burn == pytest.approx(0.9)
    monkeypatch.setenv("DSTPU_ALERT_RECOMPILE_N", "garbage")
    assert RecompileStormDetector().n == 3       # bad value → default


def test_metric_total_never_creates():
    name = "zz_probe_nonexistent_total"
    assert anomaly._metric_total(name) is None
    reg = telemetry_registry.get_registry()
    with reg._lock:
        assert name not in reg._metrics
