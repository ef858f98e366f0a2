"""CPU-mesh e2e for the alert plane and the server's watched sites
(z-sorted: heavy model work stays late in the run per the repo
convention).

An induced recompile storm and an induced SLO burn each raise exactly
one structured alert; ``/alertz`` serves them and ``/profilez`` is gone;
the flight dump embeds what was firing; and every compiled call of a
warmed server carries one observer that counts one warm-up compile a
site, no recompile, and no executable once the shapes have been seen.
"""
import json
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import ContinuousBatcher
from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config
from deepspeed_tpu.telemetry import anomaly, flightrec, recompile
from deepspeed_tpu.telemetry import registry as telemetry_registry
from deepspeed_tpu.telemetry.exporter import TelemetryExporter

from .simple_model import seeded_params


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


def _build_batcher(n_slots=2, max_tokens=64, **kw):
    cfg = gpt2_config("gpt2-tiny")
    model = GPT2LMHeadModel(cfg)
    params = seeded_params(model)
    paged = {"prefix_cache": {"page_tokens": 8, "n_pages": 64}} \
        if kw.get("paged_decode") else {}
    eng = deepspeed_tpu.init_inference(model=model, params=params,
                                       max_tokens=max_tokens, **paged)
    return ContinuousBatcher(eng, n_slots=n_slots, **kw), cfg


def _run_some(batcher, cfg, n=6, new=8, ticks=4, seed=0, **kw):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=(8,)).astype(np.int32)
               for _ in range(n)]
    return batcher.run(prompts, max_new_tokens=new, ticks=ticks, **kw)


def test_profilez_and_alertz_endpoints():
    batcher, cfg = _build_batcher()
    batcher.warmup_windows(2)
    _run_some(batcher, cfg, n=4)
    exp = TelemetryExporter(port=0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as gone:
            urllib.request.urlopen(f"{exp.url}/profilez", timeout=10)
        assert gone.value.code == 404
        with urllib.request.urlopen(f"{exp.url}/alertz", timeout=10) as r:
            alerts = json.load(r)
        assert set(alerts) == {"active", "recent", "rules"}
        assert "recompile_storm" in alerts["rules"]
        assert "attribution_drift" not in alerts["rules"]
        with urllib.request.urlopen(f"{exp.url}/statusz", timeout=10) as r:
            statusz = json.load(r)
        assert "alerts" in statusz and "attribution" not in statusz
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


def test_induced_slo_burn_raises_alert():
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


def test_flight_dump_carries_alerts(monkeypatch, tmp_path):
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
    assert "attribution" not in payload
    # the postmortem renderer answers "what was firing" in text
    text = flightrec.pretty(path)
    assert "ACTIVE alerts at dump" in text
    assert "recompile_storm" in text


# ----------------------------------------------------------------------
# the server's watched sites
# ----------------------------------------------------------------------
def _by_site(metric):
    snap = telemetry_registry.get_registry().snapshot().get(metric)
    return {} if snap is None else {
        s["labels"]["site"]: s["value"] for s in snap["samples"]}


def _serve_executables():
    snap = telemetry_registry.get_registry().snapshot().get(
        "xla_executables_total")
    return 0 if snap is None else sum(
        s["value"] for s in snap["samples"]
        if s["labels"]["span"].startswith("serve/"))


def _moved(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


@pytest.fixture(scope="module")
def served():
    """Each server kind once: warm it up, serve one round (every site's
    warm-up compile), then a second round of other prompts at the same
    shapes.  Returns, a kind, what each round moved."""
    out = {}
    for kind in ("contiguous", "paged"):
        before = (_by_site("xla_compiled_signatures_total"),
                  _by_site("xla_recompiles_total"))
        batcher, cfg = _build_batcher(paged_decode=(kind == "paged"))
        batcher.warmup_windows(4)
        _run_some(batcher, cfg, n=6, seed=1)
        first = (_by_site("xla_compiled_signatures_total"),
                 _by_site("xla_recompiles_total"), _serve_executables())
        _run_some(batcher, cfg, n=6, seed=2)
        out[kind] = {
            "warmup": _moved(first[0], before[0]),
            "recompiled": _moved(first[1], before[1]),
            "steady_signatures": _moved(
                _by_site("xla_compiled_signatures_total"), first[0]),
            "steady_recompiled": _moved(
                _by_site("xla_recompiles_total"), first[1]),
            "steady_executables": _serve_executables() - first[2]}
    return out


@pytest.mark.parametrize("kind,site,varies_by_width", [
    ("contiguous", "serving.decode[", False),
    ("contiguous", "serving.place", True),
    ("contiguous", "serving.retire", False),
    ("contiguous", "serving.first_token", True),
    ("paged", "serving.decode_paged[", False),
    ("paged", "serving.place_paged", True),
    ("paged", "serving.retire_paged", False),
    ("paged", "serving.first_token", True),
])
def test_warmed_server_site_compiles_once_and_never_again(
        served, kind, site, varies_by_width):
    moved = served[kind]
    if site.endswith("["):          # one site a window length
        warmup = {k: v for k, v in moved["warmup"].items()
                  if k.startswith(site)}
    else:
        warmup = {k: v for k, v in moved["warmup"].items() if k == site}
    assert warmup, (site, moved["warmup"])
    if not varies_by_width:         # the one warm-up compile a site
        assert set(warmup.values()) == {1}, warmup
    assert moved["recompiled"] == {}
    assert moved["steady_signatures"] == {}
    assert moved["steady_recompiled"] == {}
    assert moved["steady_executables"] == 0

