"""CPU-mesh e2e for the perf-attribution plane (z-sorted: heavy model
work stays out of the tier-1 870s window per the repo convention).

Covers the acceptance criteria: a serving run under
``DSTPU_ATTRIBUTION=1`` publishes per-executable attribution rows with
self-consistent ``mfu``/``bw_frac`` and bound-class verdicts; the
``/profilez`` and ``/alertz`` endpoints serve them; an induced
recompile storm and an induced SLO burn each raise exactly one
structured alert; and the flight dump embeds what was slow and what
was firing.
"""
import json
import urllib.request

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import ContinuousBatcher
from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config
from deepspeed_tpu.telemetry import (anomaly, attribution, flightrec,
                                     recompile)
from deepspeed_tpu.telemetry import registry as telemetry_registry
from deepspeed_tpu.telemetry.exporter import TelemetryExporter

VERDICTS = ("compute-bound", "hbm-bound", "overhead-bound")


@pytest.fixture
def fresh_plane(monkeypatch, nominal_cpu_physics):
    """A private attribution plane, sampled every window, enabled —
    swapped in for the module singleton so process-wide state from
    other tests can't leak into row assertions.  Rows are judged against
    the nominal CPU row (the tables themselves carry chips only)."""
    monkeypatch.setenv(attribution.SAMPLE_ENV, "1")
    plane = attribution.AttributionPlane()
    plane.enable(True)
    monkeypatch.setattr(attribution, "_default", plane)
    yield plane


@pytest.fixture(autouse=True)
def _fresh_anomaly(monkeypatch):
    """Swap in a fresh module anomaly engine per test (the
    ``test_zadmission`` fixture): the induced SLO burn below genuinely
    fires ``slo_burn`` on whatever engine is current, and an alert left
    ACTIVE on the process singleton would alert-promote traces in
    suites that run after this file in the same pytest process
    (``test_zreqtrace`` was the observed victim)."""
    monkeypatch.setattr(anomaly, "_default", anomaly.AnomalyEngine())
    yield


def _build_batcher(n_slots=2, max_tokens=64):
    cfg = gpt2_config("gpt2-tiny")
    model = GPT2LMHeadModel(cfg)
    params = jax.tree_util.tree_map(
        lambda x: getattr(x, "value", x),
        model.init(jax.random.PRNGKey(0),
                   np.zeros((1, 8), np.int32))["params"],
        is_leaf=lambda x: hasattr(x, "names") and hasattr(x, "value"))
    eng = deepspeed_tpu.init_inference(model=model, params=params,
                                       max_tokens=max_tokens)
    return ContinuousBatcher(eng, n_slots=n_slots), cfg


def _run_some(batcher, cfg, n=6, new=8, ticks=4, seed=0, **kw):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=(8,)).astype(np.int32)
               for _ in range(n)]
    return batcher.run(prompts, max_new_tokens=new, ticks=ticks, **kw)


def test_serving_publishes_selfconsistent_rows(fresh_plane):
    batcher, cfg = _build_batcher()
    batcher.warmup_windows(4)
    _run_some(batcher, cfg)
    snap = fresh_plane.snapshot()
    rows = snap["rows"]
    # AOT compile points alone give a broad cost table: decode windows,
    # first_token/place admission fns, retire
    sites = {r["site"] for r in rows}
    assert any(s.startswith("serving.decode[") for s in sites)
    assert "serving.retire" in sites
    assert any(s.startswith("serving.first_token[") for s in sites)
    measured = [r for r in rows if r["measured_ms"] is not None
                and r["verdict"] in VERDICTS]
    assert measured, f"no measured verdict rows in {sites}"
    for r in measured:
        # every measured row carries the full tuple and its fractions
        # recompute from its own fields + the snapshot's physics
        assert r["flops"] > 0 and r["hbm_bytes"] > 0
        assert r["mfu"] == pytest.approx(
            r["flops"] / (r["measured_ms"] / 1e3 * snap["peak_flops"]),
            rel=1e-3)
        assert r["bw_frac"] == pytest.approx(
            r["hbm_bytes"] / (r["measured_ms"] / 1e3
                              * snap["hbm_bytes_s"]), rel=1e-3)
    # the decode window must be among the measured rows (the hot path)
    assert any(r["site"].startswith("serving.decode[") for r in measured)
    # prefill chunks were sampled via the lazy harvest path
    assert any(r["site"].startswith("serving.prefill[") for r in measured)


def test_profilez_and_alertz_endpoints(fresh_plane):
    batcher, cfg = _build_batcher()
    batcher.warmup_windows(2)
    _run_some(batcher, cfg, n=4)
    exp = TelemetryExporter(port=0).start()
    try:
        with urllib.request.urlopen(f"{exp.url}/profilez", timeout=10) as r:
            prof = json.load(r)
        assert prof["enabled"] is True
        assert prof["rows"] and any(
            row["measured_ms"] is not None for row in prof["rows"])
        with urllib.request.urlopen(f"{exp.url}/alertz", timeout=10) as r:
            alerts = json.load(r)
        assert set(alerts) == {"active", "recent", "rules"}
        assert "recompile_storm" in alerts["rules"]
        # /statusz carries the compact sections too
        with urllib.request.urlopen(f"{exp.url}/statusz", timeout=10) as r:
            statusz = json.load(r)
        assert "attribution" in statusz and "alerts" in statusz
        assert statusz["attribution"]["measured"] >= 1
    finally:
        exp.stop()


def test_induced_recompile_storm_raises_exactly_one_alert():
    det = anomaly.RecompileStormDetector(n=3, window_s=600)
    eng = anomaly.AnomalyEngine(detectors=[det])
    c_before = telemetry_registry.get_registry().counter(
        "alerts_total", labelnames=("rule",)).labels(
        rule="recompile_storm").value
    eng.observe(force=True)           # baseline BEFORE the storm
    # a watched hot loop fed drifting shapes IS a storm: each new
    # signature past warm-up increments xla_recompiles_total
    watched = recompile.watch(jax.jit(lambda x: x * 2),
                              name="zattr.storm_site")
    for n in (4, 8, 16, 32, 64):
        np.asarray(watched(np.ones((n,), np.float32)))
    evs = eng.observe(force=True)     # storm visible in the delta
    evs += eng.observe(force=True)    # still storming: no re-fire
    fires = [e for e in evs if e["state"] == "firing"]
    assert len(fires) == 1, fires
    assert fires[0]["rule"] == "recompile_storm"
    assert fires[0]["value"] >= 3
    assert telemetry_registry.get_registry().counter(
        "alerts_total", labelnames=("rule",)).labels(
        rule="recompile_storm").value == c_before + 1
    assert "recompile_storm" in eng.active()


def test_induced_slo_burn_raises_alert(fresh_plane):
    batcher, cfg = _build_batcher()
    # SLO bounds no real request can meet: every retirement violates
    batcher.set_slo(ttft_ms=0.0001, tpot_ms=0.0001)
    det = anomaly.SloBurnDetector(burn=0.5, window_s=600, min_events=4)
    eng = anomaly.AnomalyEngine(detectors=[det])
    eng.observe(force=True)           # baseline before the burn
    _run_some(batcher, cfg, n=6)
    evs = eng.observe(force=True)
    fires = [e for e in evs if e["state"] == "firing"]
    assert [e["rule"] for e in fires] == ["slo_burn"]
    assert fires[0]["value"] >= 0.5
    assert fires[0]["detail"]["events"] >= 4


def test_flight_dump_carries_attribution_and_alerts(
        fresh_plane, monkeypatch, tmp_path):
    batcher, cfg = _build_batcher()
    batcher.warmup_windows(2)
    _run_some(batcher, cfg, n=4)
    # a fired engine swapped in as the module singleton (the dump pulls
    # anomaly.get_engine())
    det = anomaly.RecompileStormDetector(n=1, window_s=600)

    class _Eng(anomaly.AnomalyEngine):
        def _sample(self, now):
            pass

    eng = _Eng(detectors=[det])
    eng.series["recompiles"].add(0.0, 0.0)
    eng.series["recompiles"].add(1.0, 2.0)
    eng.observe(now=1.0, force=True)
    assert eng.active()
    monkeypatch.setattr(anomaly, "_default", eng)
    rec = flightrec.FlightRecorder(str(tmp_path))
    path = rec.dump("test")
    assert path is not None
    payload = json.load(open(path))
    assert payload["alerts"]["active"][0]["rule"] == "recompile_storm"
    rows = payload["attribution"]["rows"]
    assert any(r["measured_ms"] is not None for r in rows)
    # the postmortem renderer answers "what was slow and what was
    # firing" in text
    text = flightrec.pretty(path)
    assert "ACTIVE alerts at dump" in text
    assert "recompile_storm" in text
    assert "attribution (measured executables" in text


def test_attribution_off_is_default_and_rowless(monkeypatch):
    monkeypatch.delenv(attribution.ATTRIBUTION_ENV, raising=False)
    plane = attribution.AttributionPlane()
    monkeypatch.setattr(attribution, "_default", plane)
    batcher, cfg = _build_batcher()
    _run_some(batcher, cfg, n=2, new=4, ticks=2)
    assert not plane.enabled()
    # no sampling hooks ran: no measured rows (warmup wasn't called so
    # no AOT rows either — the plane is fully passive)
    assert all(r["measured_ms"] is None
               for r in plane.snapshot()["rows"])
