"""Unified telemetry layer: registry counter/gauge/histogram semantics,
Prometheus text rendering, Chrome-trace JSON validity, and the XLA
recompilation watchdog (fires exactly once per forced shape change,
stays silent on a stable hot loop)."""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.telemetry import recompile, trace
from deepspeed_tpu.telemetry.registry import Registry, get_registry

from .simple_model import tiny_gpt2_engine


@pytest.fixture(autouse=True)
def clean_trace():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


# ----------------------------------------------------------------------
# registry semantics
# ----------------------------------------------------------------------
def test_counter_semantics():
    r = Registry()
    c = r.counter("reqs_total", "requests")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create returns the same handle
    assert r.counter("reqs_total") is c
    # re-registering under another type is an error
    with pytest.raises(ValueError):
        r.gauge("reqs_total")


def test_gauge_semantics():
    r = Registry()
    g = r.gauge("depth")
    g.set(7)
    g.inc()
    g.dec(3)
    assert g.value == 5.0


def test_labels():
    r = Registry()
    c = r.counter("hits_total", labelnames=("site",))
    c.labels(site="a").inc()
    c.labels(site="a").inc()
    c.labels(site="b").inc()
    assert c.labels(site="a").value == 2.0
    assert c.total() == 3.0
    with pytest.raises(ValueError):
        c.inc()              # labelled metric needs .labels(...)
    with pytest.raises(ValueError):
        c.labels(wrong="x")


def test_histogram_semantics():
    r = Registry()
    h = r.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    h.observe(float("nan"))     # dropped, must not poison sum/count
    child = h._default_child()
    assert child.count == 4
    assert child.sum == pytest.approx(55.55)
    cum = dict(child.cumulative())
    assert cum[0.1] == 1 and cum[1.0] == 2 and cum[10.0] == 3
    assert cum[float("inf")] == 4


def test_snapshot_json_roundtrip():
    r = Registry()
    r.counter("a_total").inc(2)
    r.gauge("b").set(1.5)
    r.histogram("c_seconds", buckets=(1.0,)).observe(0.5)
    snap = r.snapshot()
    assert json.loads(json.dumps(snap)) == snap
    assert snap["a_total"]["samples"][0]["value"] == 2
    assert snap["c_seconds"]["samples"][0]["count"] == 1


def _parse_prometheus(text):
    """Tiny exposition-format parser: {(name, labelstring): value}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        metric, value = line.rsplit(" ", 1)
        out[metric] = float(value)
    return out


def test_prometheus_render_roundtrip():
    """Registry snapshot values survive the Prometheus text renderer."""
    r = Registry()
    c = r.counter("req_total", "reqs", labelnames=("site",))
    c.labels(site="train").inc(3)
    c.labels(site='we"ird\nsite').inc()     # label escaping
    r.gauge("depth").set(2.5)
    h = r.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = r.render_prometheus()
    parsed = _parse_prometheus(text)
    assert parsed['req_total{site="train"}'] == 3
    assert parsed["depth"] == 2.5
    assert parsed['lat_seconds_bucket{le="0.1"}'] == 1
    assert parsed['lat_seconds_bucket{le="1"}'] == 2
    assert parsed['lat_seconds_bucket{le="+Inf"}'] == 2
    assert parsed["lat_seconds_count"] == 2
    assert parsed["lat_seconds_sum"] == pytest.approx(0.55)
    # every snapshot scalar appears in the rendering
    snap = r.snapshot()
    for name, entry in snap.items():
        if entry["type"] != "histogram":
            for s in entry["samples"]:
                assert any(m.startswith(name) for m in parsed), name


def test_histogram_bucket_conflict_raises():
    r = Registry()
    r.histogram("lat_seconds", buckets=(0.1, 1.0))
    with pytest.raises(ValueError):
        r.histogram("lat_seconds", buckets=(0.5, 5.0))
    # same buckets: same handle
    assert r.histogram("lat_seconds", buckets=(0.1, 1.0)) is not None


def test_registry_dump(tmp_path):
    r = Registry()
    r.counter("x_total").inc()
    path = str(tmp_path / "m" / "metrics.json")
    r.dump(path)
    with open(path) as fh:
        data = json.load(fh)
    assert data["x_total"]["samples"][0]["value"] == 1


# ----------------------------------------------------------------------
# Chrome-trace step tracer
# ----------------------------------------------------------------------
def test_trace_disabled_records_nothing():
    with trace.span("ghost"):
        pass
    assert trace.to_json()["traceEvents"] == []


def test_trace_span_nesting_and_save(tmp_path):
    trace.enable()
    with trace.span("step", idx=0):
        with trace.span("fwd"):
            pass
        with trace.span("bwd"):
            pass
    trace.disable()
    path = str(tmp_path / "trace.json")
    trace.save(path)
    with open(path) as fh:
        data = json.load(fh)          # must be valid JSON
    events = data["traceEvents"]
    by_name = {e["name"]: e for e in events}
    assert set(by_name) == {"step", "fwd", "bwd"}
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0
    step, fwd, bwd = by_name["step"], by_name["fwd"], by_name["bwd"]
    # children nest inside the parent interval, in order
    assert step["ts"] <= fwd["ts"]
    assert fwd["ts"] + fwd["dur"] <= bwd["ts"]
    assert bwd["ts"] + bwd["dur"] <= step["ts"] + step["dur"]
    assert by_name["step"]["args"] == {"idx": 0}


def test_trace_decorator():
    trace.enable()

    @trace.span("decorated")
    def f(x):
        return x + 1

    assert f(1) == 2
    assert [e["name"] for e in trace.to_json()["traceEvents"]] == ["decorated"]


# ----------------------------------------------------------------------
# the always-on ring, parents, self time, the profiler bridge
# ----------------------------------------------------------------------
def test_ring_keeps_spans_while_chrome_tracing_is_off():
    import time

    assert not trace.enabled()
    t0 = time.perf_counter()
    with trace.span("ring/a", step=3):
        pass
    t1 = time.perf_counter()
    with trace.span("other/b"):
        pass
    assert trace.to_json()["traceEvents"] == []
    (a,) = trace.spans(prefix="ring/")
    assert a.name == "ring/a" and a.args == {"step": 3}
    # the read axis is time.perf_counter()
    assert t0 <= a.start_s <= a.end_s <= t1
    assert [s.name for s in trace.spans(since_s=t1)] == ["other/b"]
    assert [s.name for s in trace.spans(until_s=t1)] == ["ring/a"]
    assert trace.spans(prefix="ring/", since_s=t1) == []
    assert trace.totals()["ring/a"]["count"] == 1


def test_span_parents_and_self_time_on_a_nested_trio():
    import time

    with trace.span("trio/root") as root:
        with trace.span("trio/mid") as mid:
            with trace.span("trio/leaf"):
                time.sleep(0.01)
            time.sleep(0.005)
        assert trace.current() is root
    by = {s.name: s for s in trace.spans(prefix="trio/")}
    leaf, mid_s, root_s = by["trio/leaf"], by["trio/mid"], by["trio/root"]
    assert root_s.parent_id == 0 and root_s.parent is None
    assert (mid_s.parent_id, mid_s.parent) == (root_s.id, "trio/root")
    assert (leaf.parent_id, leaf.parent) == (mid_s.id, "trio/mid")
    assert len({leaf.id, mid_s.id, root_s.id}) == 3 and mid.id == mid_s.id
    tot = trace.totals()
    # self = duration minus what child spans cover
    # (two clock differences cancel: an absolute tolerance, not a relative)
    assert tot["trio/leaf"]["self_seconds"] == pytest.approx(leaf.dur_s,
                                                             abs=1e-6)
    assert tot["trio/mid"]["self_seconds"] == pytest.approx(
        mid_s.dur_s - leaf.dur_s, abs=1e-6)
    assert tot["trio/root"]["self_seconds"] == pytest.approx(
        root_s.dur_s - mid_s.dur_s, abs=1e-6)
    assert tot["trio/mid"]["self_seconds"] >= 0.004
    assert tot["trio/root"]["self_seconds"] < 0.004
    # the Chrome file carries the same ids when it is on
    trace.enable()
    with trace.span("trio/root"):
        with trace.span("trio/mid"):
            pass
    ev = {e["name"]: e for e in trace.to_json()["traceEvents"]}
    assert ev["trio/mid"]["parent_id"] == ev["trio/root"]["span_id"] != 0


def test_ring_is_bounded():
    assert trace.RING_SIZE >= 16384
    for i in range(trace.RING_SIZE + 10):
        trace.record("ring/filler", 0.0, i=i)
    kept = trace.spans(prefix="ring/filler")
    assert len(kept) == trace.RING_SIZE
    assert kept[0].args == {"i": 10}            # the oldest ten fell out
    # the totals forget nothing
    assert trace.totals()["ring/filler"]["count"] == trace.RING_SIZE + 10


def test_span_lands_on_the_host_plane_of_a_profiler_trace(tmp_path):
    """Program spans share the device trace's clock by being IN it."""
    import glob

    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace.span("unit/xplane-outer", step=1):
            with trace.span("unit/xplane-inner"):
                jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [pl for pl in ProfileData.from_file(path).planes
            if pl.name == "/host:CPU"]
    assert host
    found = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
             for pl in host for line in pl.lines for e in line.events
             if e.name.startswith("unit/xplane-")}
    assert set(found) == {"unit/xplane-outer", "unit/xplane-inner"}
    (o0, o1), (i0, i1) = found["unit/xplane-outer"], found["unit/xplane-inner"]
    assert o0 <= i0 <= i1 <= o1


# ----------------------------------------------------------------------
# compile events by phase and by open span
# ----------------------------------------------------------------------
def _compile_counters(span):
    snap = get_registry().snapshot()
    secs = {s["labels"]["phase"]: s["value"]
            for s in snap.get("xla_compile_seconds_total",
                              {"samples": []})["samples"]
            if s["labels"]["span"] == span}
    n = sum(s["value"] for s in snap.get("xla_executables_total",
                                         {"samples": []})["samples"]
            if s["labels"]["span"] == span)
    return secs, n


def test_compile_events_are_booked_to_the_open_span():
    import time

    inners = [jax.jit(lambda x, i=i: jnp.sin(x) * i) for i in range(40)]

    @jax.jit
    def fresh(x):
        for inner in inners:
            x = inner(x) + jnp.where(x > 0, x, 0.0)
        return x

    x = jnp.ones(7)                 # its own executables build out here
    secs0, n0 = _compile_counters("unit/compiles")
    assert n0 == 0 and not secs0
    t0 = time.perf_counter()
    with trace.span("unit/compiles"):
        fresh(x).block_until_ready()
    wall = time.perf_counter() - t0
    secs, n = _compile_counters("unit/compiles")
    assert n == 1
    assert all(secs[ph] > 0 for ph in ("trace", "lower", "backend"))
    # forty jits and the jnp functions trace INSIDE `fresh`'s trace: each
    # second is booked once, so the phases together fit in the span
    assert sum(secs.values()) <= wall
    (rec,) = [s for s in trace.spans(prefix="compile/backend")
              if s.parent == "unit/compiles"]
    assert rec.args["fun"] == "jit(fresh)" and rec.args["how"] == "built"
    # a second call builds nothing
    with trace.span("unit/compiles"):
        fresh(x).block_until_ready()
    assert _compile_counters("unit/compiles") == (secs, n)


# ----------------------------------------------------------------------
# the trainer's and the scheduler's span trees
# ----------------------------------------------------------------------
def test_train_batch_leaves_one_step_span_with_three_children():
    from deepspeed_tpu.comm import mesh as mesh_mod

    mesh_mod.set_mesh(None)
    try:
        import deepspeed_tpu
        from .simple_model import SimpleModel

        engine, _, _, _ = deepspeed_tpu.initialize(
            model=SimpleModel(),
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
        engine.init_params()
        rng = np.random.default_rng(0)
        b = engine.train_batch_size

        def batches():
            while True:
                x = rng.normal(size=(b, 16)).astype(np.float32)
                yield {"x": x, "y": 0.1 * x}

        it = batches()
        for _ in range(3):
            engine.train_batch(data_iter=it)
        assert [s.name for s in trace.spans(prefix="init/")] == [
            "init/engine", "init/params"]
        steps = trace.spans(prefix="train/step")
        assert [s.args["step"] for s in steps] == [0, 1, 2]
        for step in steps:
            kids = [s for s in trace.spans(prefix="train/")
                    if s.parent_id == step.id]
            assert [k.name for k in kids] == [
                "train/next-batch", "train/device-put", "train/dispatch"]
            assert all(k.parent == "train/step" for k in kids)
            assert sum(k.dur_s for k in kids) <= step.dur_s
        # the first step compiled, and says so under its dispatch span
        secs, n = _compile_counters("train/dispatch")
        assert n >= 1 and secs["backend"] > 0
        first = [s for s in trace.spans(prefix="compile/backend")
                 if s.parent == "train/dispatch"]
        assert first and first[0].start_s >= steps[0].start_s
        assert first[-1].end_s <= steps[0].end_s
    finally:
        mesh_mod.set_mesh(None)


def test_batcher_step_leaves_the_serve_tree_with_the_requests_uid():
    from deepspeed_tpu.comm import mesh as mesh_mod

    mesh_mod.set_mesh(None)
    try:
        import deepspeed_tpu
        from deepspeed_tpu.inference.serving import ContinuousBatcher
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

        eng = tiny_gpt2_engine()
        batcher = ContinuousBatcher(eng, n_slots=2)
        prompt = np.arange(5, dtype=np.int32)
        uid = batcher.submit(prompt, max_new_tokens=3)
        while batcher.pending:
            batcher.step(2)
        mine = [s for s in trace.spans(prefix="serve/")
                if uid in ((s.args or {}).get("uids") or ())]
        assert {s.name for s in mine} == {
            "serve/prefill", "serve/admit", "serve/decode-tick",
            "serve/retire"}
        steps = trace.spans(prefix="serve/step")
        assert steps and steps[0].args["ticks"] == 2
        ids = {s.id for s in steps}
        by_name = {}
        for s in trace.spans(prefix="serve/"):
            by_name.setdefault(s.name, []).append(s)
        # every child hangs off a step, directly or through its parent
        for name in ("serve/admit", "serve/prefill-batch",
                     "serve/decode-tick"):
            assert all(s.parent_id in ids for s in by_name[name]), name
        assert all(s.parent == "serve/prefill-batch"
                   for s in by_name["serve/prefill"])
        assert all(s.parent == "serve/decode-tick"
                   for s in by_name["serve/fetch"])
        assert by_name["serve/prefill-batch"][0].args["rows"] == 1
        assert set(by_name) == {
            "serve/step", "serve/admit", "serve/prefill-batch",
            "serve/prefill", "serve/decode-tick", "serve/fetch",
            "serve/retire"}
    finally:
        mesh_mod.set_mesh(None)


def test_device_scopes_are_metadata_only(monkeypatch):
    """``loss_head`` / ``grad_clip`` / ``optimizer`` / ``embed`` reach the
    train step's op names and change not one instruction."""
    import contextlib

    from deepspeed_tpu.comm import mesh as mesh_mod

    def lowered():
        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

        mesh_mod.set_mesh(None)
        cfg = gpt2_config("gpt2-tiny", dtype=jnp.float32, scan_layers=False,
                          loss_chunk=64)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2LMHeadModel(cfg),
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_clipping": 1.0,
                    "zero_optimization": {"stage": 3},
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "mesh": {"fsdp": -1}})
        engine.init_params()
        ids = np.zeros((engine.train_batch_size, 16), np.int32)
        batch = engine._shard_batch({"input_ids": ids, "labels": ids})
        return engine._compiled_train_step.lower(engine._state, batch)

    try:
        with_scopes = lowered()
        monkeypatch.setattr(trace, "device_span",
                            lambda name: contextlib.nullcontext())
        without = lowered()
    finally:
        mesh_mod.set_mesh(None)
    named = with_scopes.as_text(debug_info=True)
    for scope in ("loss_head", "grad_clip", "optimizer", "embed",
                  "zero/scatter"):
        assert f"/{scope}/" in named, scope
        assert f"/{scope}/" not in without.as_text(debug_info=True), scope
    # flax already stamps the module path; nothing was added for these
    assert "/h_0/attn/" in named and "/h_1/mlp/" in named
    a, b = with_scopes.as_text(), without.as_text()
    assert len(a.splitlines()) == len(b.splitlines())
    assert a == b


# ----------------------------------------------------------------------
# recompilation watchdog
# ----------------------------------------------------------------------
def _site_value(registry, metric, site):
    c = registry.counter(metric, labelnames=("site",))
    return c.labels(site=site).value


def _replicated_and_sharded():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("d",))
    x = np.zeros((len(jax.devices()) * 2,), np.float32)
    return (jax.device_put(x, NamedSharding(mesh, P())),
            jax.device_put(x, NamedSharding(mesh, P("d"))))


def _cause_shape():
    return (jax.jit(lambda x: x + 1),
            (jnp.zeros((4,), jnp.float32),), (jnp.zeros((8,), jnp.float32),))


def _cause_dtype():
    return (jax.jit(lambda x: x + 1),
            (jnp.zeros((4,), jnp.float32),), (jnp.zeros((4,), jnp.int32),))


def _cause_weak_type():
    # the python float that became an array: jnp.asarray(1.0) is a
    # weakly typed float32, jnp.float32(1.0) a strongly typed one
    return (jax.jit(lambda x: x * 2),
            (jnp.float32(1.0),), (jnp.asarray(1.0),))


def _cause_static_argument():
    x = jnp.zeros((4,), jnp.float32)
    return (jax.jit(lambda x, n: x * n, static_argnums=1), (x, 2), (x, 3))


def _cause_sharding():
    replicated, sharded = _replicated_and_sharded()
    return jax.jit(lambda x: x + 1), (replicated,), (sharded,)


@pytest.mark.parametrize("cause", [
    _cause_shape, _cause_dtype, _cause_weak_type, _cause_static_argument,
    _cause_sharding], ids=lambda c: c.__name__[len("_cause_"):])
def test_watchdog_counts_a_recompile_by_cause(cause):
    """Whatever made the warm loop compile again, it is one recompile:
    a new signature (shape, dtype, weak type), or a known one that the
    jit cache keys further (a static argument's value, an input's
    sharding)."""
    fn, first, changed = cause()
    reg = Registry()
    f = recompile.RecompileWatchdog(registry=reg).watch(fn, "unit.cause")
    f(*first)                                # warm-up compile
    f(*first)                                # steady: the site settles
    assert _site_value(reg, "xla_recompiles_total", "unit.cause") == 0
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.cause") == 1
    f(*changed)                              # the forced change
    assert _site_value(reg, "xla_recompiles_total", "unit.cause") == 1
    f(*changed)                              # now-known executables
    f(*first)
    assert _site_value(reg, "xla_recompiles_total", "unit.cause") == 1
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.cause") == 2


def test_watchdog_silent_on_stable_loop():
    reg = Registry()
    dog = recompile.RecompileWatchdog(registry=reg)
    f = dog.watch(jax.jit(lambda x, y: x * y), "unit.stable")
    for i in range(10):
        f(jnp.full((4,), float(i)), jnp.float32(i))
    assert _site_value(reg, "xla_recompiles_total", "unit.stable") == 0
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.stable") == 1
    assert dog._last_warn == {}       # no warning ever rate-limited in


def test_watchdog_warn_false_counts_compiles_only():
    reg = Registry()
    dog = recompile.RecompileWatchdog(registry=reg)
    f = dog.watch(jax.jit(lambda x: x + 1), "unit.varying", warn=False)
    f(jnp.zeros((2,)))
    f(jnp.zeros((4,)))
    f(jnp.zeros((8,)))
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.varying") == 3
    assert _site_value(reg, "xla_recompiles_total", "unit.varying") == 0


def test_watchdog_wrapper_is_transparent():
    f = jax.jit(lambda x: x * 2)
    w = recompile.watch(f, "unit.transparent")
    assert float(w(jnp.float32(3))) == 6.0
    assert w.lower(jnp.float32(1)) is not None     # attr passthrough


def test_watchdog_known_signature_compiling_before_the_site_settles():
    """A real ``jax.jit`` whose input sharding changes at unchanged
    shapes BEFORE the site has had a call that compiled nothing is
    warm-up churn (eager-built buffers replaced by committed jit
    outputs), not a recompile; by-cause[sharding] holds the same change
    after the site settled."""
    replicated, sharded = _replicated_and_sharded()
    reg = Registry()
    f = recompile.RecompileWatchdog(registry=reg).watch(
        jax.jit(lambda x: x + 1), "unit.warmup")
    f(replicated)
    f(sharded)                  # second executable, same signature
    assert f._cache_size() == 2
    f(sharded)
    f(replicated)
    assert _site_value(reg, "xla_recompiles_total", "unit.warmup") == 0
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.warmup") == 1


def test_watchdog_steady_call_signs_nothing(monkeypatch):
    """The cost the watchdog used to have: a steady call through it
    flattens no tree and asks no ``_cache_size()``, however many leaves
    the arguments have."""
    signed, cache_size_asked = [], []
    real_sig = recompile._tree_sig
    monkeypatch.setattr(recompile, "_tree_sig",
                        lambda tree: signed.append(1) or real_sig(tree))

    class Jit:
        fn = staticmethod(jax.jit(
            lambda tree, x: x + sum(tree[k] for k in ("p0", "p2999"))))

        def __call__(self, *args):
            return self.fn(*args)

        def _cache_size(self):
            cache_size_asked.append(1)
            return self.fn._cache_size()

    tree = {f"p{i}": jnp.float32(i) for i in range(3000)}
    reg = Registry()
    f = recompile.RecompileWatchdog(registry=reg).watch(Jit(), "unit.steady")
    assert float(f(tree, jnp.float32(1))) == 3000.0
    assert len(signed) == 1                  # the call that compiled
    for i in range(5):
        f(tree, jnp.float32(i))
    assert len(signed) == 1 and not cache_size_asked
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.steady") == 1


def test_watchdog_compile_on_another_thread_is_not_this_sites():
    import threading

    def compile_elsewhere(x):
        t = threading.Thread(
            target=lambda: jax.jit(lambda y: y * 3)(jnp.zeros((5,))))
        t.start()
        t.join()
        return x

    reg = Registry()
    dog = recompile.RecompileWatchdog(registry=reg)
    f = dog.watch(compile_elsewhere, "unit.other_thread")
    f(jnp.zeros((4,)))
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.other_thread") == 0
    # the same compile on the calling thread is this site's
    g = dog.watch(lambda x: jax.jit(lambda y: y * 5)(x), "unit.same_thread")
    g(jnp.zeros((4,)))
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.same_thread") == 1


def test_watchdog_nested_calls_bill_the_inner_site():
    reg = Registry()
    dog = recompile.RecompileWatchdog(registry=reg)
    inner = dog.watch(jax.jit(lambda x: x + 1), "unit.inner")
    passes_through = dog.watch(lambda x: inner(x), "unit.outer")
    own = jax.jit(lambda x: x * 2)
    compiles_too = dog.watch(lambda x: own(inner(x)), "unit.outer_own")
    passes_through(jnp.zeros((4,)))
    passes_through(jnp.zeros((4,)))
    passes_through(jnp.zeros((8,)))          # inner recompiles
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.inner") == 2
    assert _site_value(reg, "xla_recompiles_total", "unit.inner") == 1
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.outer") == 0
    compiles_too(jnp.zeros((16,)))           # one executable each
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.inner") == 3
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.outer_own") == 1


def test_watchdog_warning_names_the_leaf_that_changed(monkeypatch):
    warned = []
    monkeypatch.setattr(recompile.logger, "warning", warned.append)
    dog = recompile.RecompileWatchdog(registry=Registry())
    f = dog.watch(jax.jit(lambda batch, step: batch["ids"].sum() + step),
                  "unit.named")
    small = {"ids": jnp.zeros((2, 8), jnp.int32),
             "mask": jnp.ones((2, 8), jnp.int32)}
    other = {"ids": jnp.zeros((4, 8), jnp.int32),
             "mask": jnp.ones((4, 8), jnp.int32)}
    ragged = {"ids": jnp.zeros((2, 8), jnp.int32),
              "mask": jnp.ones((2, 5), jnp.int32)}
    f(small, jnp.int32(0))
    f(other, jnp.int32(1))
    assert len(warned) == 1 and "+1 more" in warned[0]
    dog._last_warn.clear()                   # past the rate limit
    f(ragged, jnp.int32(2))
    # diffed against the NEAREST compiled signature (small: one leaf
    # differs), not the last one (other: both differ)
    assert len(warned) == 2
    assert "unit.named" in warned[1] and "['mask']" in warned[1]
    assert "(2, 8)" in warned[1] and "(2, 5)" in warned[1]
    assert "more" not in warned[1]


def test_watchdog_signs_a_donated_state(monkeypatch):
    """The arguments are signed AFTER the call that compiled, when a
    donated state's buffers are gone: shape, dtype and weak type survive
    donation, so the loop below is one signature, and the state that
    grew is named with the shapes it had."""
    warned = []
    monkeypatch.setattr(recompile.logger, "warning", warned.append)
    reg = Registry()
    f = recompile.RecompileWatchdog(registry=reg).watch(
        jax.jit(lambda s, b: ({"w": s["w"] + b, "n": s["n"] + 1}, b.sum()),
                donate_argnums=(0,)), "unit.donated")
    state = {"w": jnp.zeros((4,), jnp.bfloat16), "n": jnp.int32(0)}
    for _ in range(3):
        donated = state
        state, _ = f(state, jnp.ones((4,), jnp.bfloat16))
    assert donated["w"].is_deleted()
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.donated") == 1
    assert _site_value(reg, "xla_recompiles_total", "unit.donated") == 0
    grown = {"w": jnp.zeros((8,), jnp.bfloat16), "n": jnp.int32(0)}
    f(grown, jnp.ones((8,), jnp.bfloat16))
    assert grown["w"].is_deleted()
    assert _site_value(reg, "xla_recompiles_total", "unit.donated") == 1
    assert "['w']: ((4,), 'bfloat16', False) -> ((8,), 'bfloat16', False)" \
        in warned[0]


def test_watchdog_bills_the_compiling_call_to_goodput():
    from deepspeed_tpu.telemetry import goodput

    f = recompile.watch(jax.jit(lambda x: jnp.tanh(x) @ x.T),
                        "unit.goodput")
    x = jnp.ones((16, 16))
    before = goodput.summary()["recompile_s"]
    f(x)
    compiled = goodput.summary()["recompile_s"]
    assert compiled > before
    for _ in range(3):
        f(x)
    assert goodput.summary()["recompile_s"] == compiled


# ----------------------------------------------------------------------
# integrations: monitor sink, throughput timer
# ----------------------------------------------------------------------
def test_monitor_registry_sink():
    from deepspeed_tpu.monitor.monitor import MonitorConfig, MonitorMaster

    m = MonitorMaster(MonitorConfig())
    assert not m.enabled            # no external writer configured …
    m.write_events([("Telemetry/test_sink", 2.25, 40)])
    reg = get_registry()
    g = reg.gauge("monitor_event", labelnames=("label",))
    assert g.labels(label="Telemetry/test_sink").value == 2.25
    gs = reg.gauge("monitor_event_samples", labelnames=("label",))
    assert gs.labels(label="Telemetry/test_sink").value == 40


def test_throughput_timer_publishes():
    from deepspeed_tpu.utils.timer import ThroughputTimer

    t = ThroughputTimer(batch_size=4, start_step=0, steps_per_output=2,
                        metric_prefix="ttimer_test")
    for _ in range(4):
        t.start()
        t.stop()
    reg = get_registry()
    assert reg.counter("ttimer_test_steps_total").value == 4
    assert reg.counter("ttimer_test_samples_total").value == 16
    assert reg.gauge("ttimer_test_samples_per_sec").value > 0


# ----------------------------------------------------------------------
# end-to-end smoke: train + serve emit a valid trace and a non-empty
# registry snapshot (the acceptance-criteria run)
# ----------------------------------------------------------------------
def test_train_serve_smoke_emits_trace_and_metrics(tmp_path):
    from deepspeed_tpu.comm import mesh as mesh_mod

    mesh_mod.set_mesh(None)
    try:
        trace.enable()
        # -- train: 2 steps on the tiny MSE model ----------------------
        import deepspeed_tpu
        from .simple_model import SimpleModel

        engine, _, _, _ = deepspeed_tpu.initialize(
            model=SimpleModel(),
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
        engine.init_params()
        rng = np.random.default_rng(0)
        b = engine.train_batch_size
        for i in range(2):
            x = rng.normal(size=(b, 16)).astype(np.float32)
            engine.train_batch({"x": x, "y": 0.1 * x})

        # -- serve: 2 requests through the continuous batcher ----------
        mesh_mod.set_mesh(None)
        from deepspeed_tpu.inference.serving import ContinuousBatcher
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

        eng = tiny_gpt2_engine()
        batcher = ContinuousBatcher(eng, n_slots=2)
        prompts = [rng.integers(0, 512, size=(5,)).astype(np.int32)
                   for _ in range(2)]
        outs = batcher.run(prompts, ticks=4, max_new_tokens=4)
        assert all(len(o) == 9 for o in outs)

        trace.disable()
        path = trace.save(str(tmp_path / "trace.json"))
        with open(path) as fh:
            data = json.load(fh)
        names = {e["name"] for e in data["traceEvents"]}
        assert len(names) >= 3, names
        assert {"train/dispatch", "serve/prefill",
                "serve/decode-tick"} <= names

        snap = get_registry().snapshot()
        assert snap, "registry snapshot empty after train+serve"
        assert snap["train_steps_total"]["samples"][0]["value"] >= 2
        assert snap["serving_requests_completed_total"][
            "samples"][0]["value"] >= 2
        # the steady loops did not recompile after warm-up
        rec = [s for s in snap["xla_recompiles_total"]["samples"]
               if s["value"] > 0]
        assert rec == [], rec
        # and the snapshot renders to Prometheus text cleanly
        text = get_registry().render_prometheus()
        assert "train_steps_total" in text
    finally:
        mesh_mod.set_mesh(None)


def test_serving_parked_batch_shrinks_to_single_row():
    """Once a parked prefill batch is down to one pending row, the B-row
    cache reference is dropped (the row is sliced into its own 1-row
    cache) — and the emitted tokens are unchanged."""
    from deepspeed_tpu.comm import mesh as mesh_mod

    mesh_mod.set_mesh(None)
    try:
        import deepspeed_tpu
        from deepspeed_tpu.inference.serving import ContinuousBatcher
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

        eng = tiny_gpt2_engine()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 512, size=(6,)).astype(np.int32)
                   for _ in range(4)]

        b = ContinuousBatcher(eng, n_slots=1, prefill_ahead=4)
        # occupy the only slot, then park 3 equal-length prompts in ONE
        # batched prefill
        uids = [b.submit(prompts[0], max_new_tokens=8)]
        b.step(1)
        uids += [b.submit(p, max_new_tokens=3) for p in prompts[1:]]
        saw_single_row = False
        for _ in range(40):
            b.step(1)
            widths = [int(e[3].shape[0]) for e in b._parked]
            if widths == [1]:
                saw_single_row = True     # last pending row got its own
            if not b.pending:             # 1-row cache (B-row freed)
                break
        assert saw_single_row
        assert not b.pending

        # exactness: same outputs as a batcher that never parks
        mesh_mod.set_mesh(None)
        ref = ContinuousBatcher(eng, n_slots=1, prefill_ahead=0)
        r0 = ref.run([prompts[0]], ticks=4, max_new_tokens=8)
        rrest = ref.run(prompts[1:], ticks=4, max_new_tokens=3)
        for uid, expect in zip(uids, r0 + rrest):
            np.testing.assert_array_equal(b._finished[uid], expect)
    finally:
        mesh_mod.set_mesh(None)
