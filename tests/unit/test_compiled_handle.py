"""The recompile watchdog keeps the executable a ``staged`` site runs
(``telemetry/recompile.py _Staged``): one executable a signature, the
steady call the kept ``Compiled`` and nothing else, its memory booked when
it is made, and the engine's readers over that handle
(``compiled_step``, ``record_memory_profile``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.telemetry import get_registry, memory, recompile
from deepspeed_tpu.telemetry.registry import Registry


def _site_value(registry, metric, site):
    return registry.counter(metric, labelnames=("site",)).labels(
        site=site).value


def _executables() -> float:
    """Every executable the process has made so far, built or fetched."""
    entry = get_registry().snapshot().get("xla_executables_total")
    return sum(s["value"] for s in (entry or {"samples": []})["samples"])


def _step(state, batch):
    return ({"w": state["w"] + batch.sum(), "n": state["n"] + 1},
            {"loss": batch.mean()})


def _staged(reg, name, **jit_kw):
    return recompile.RecompileWatchdog(registry=reg).watch(
        jax.jit(_step, **jit_kw), name, staged=True)


def _state(n=4):
    return {"w": jnp.zeros((n,), jnp.float32), "n": jnp.int32(0)}


def test_two_calls_make_one_executable_and_donate_the_state():
    reg = Registry()
    f = _staged(reg, "unit.staged", donate_argnums=(0,))
    assert f.compiled is None
    batch = jnp.ones((4,), jnp.float32)
    jax.block_until_ready(batch)
    first = _state()
    jax.block_until_ready(first)
    before = _executables()
    state, _ = f(first, batch)
    handle = f.compiled
    assert isinstance(handle, jax.stages.Compiled)
    second = state
    state, metrics = f(state, batch)
    assert _executables() - before == 1
    assert f.compiled is handle
    assert first["w"].is_deleted() and second["w"].is_deleted()
    assert float(state["w"][0]) == 8.0 and int(state["n"]) == 2
    assert float(metrics["loss"]) == 1.0
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.staged") == 1
    assert _site_value(reg, "xla_recompiles_total", "unit.staged") == 0


def test_a_changed_signature_makes_a_second_handle_and_one_recompile(
        monkeypatch):
    warned = []
    monkeypatch.setattr(recompile.logger, "warning", warned.append)
    reg = Registry()
    f = _staged(reg, "unit.grown", donate_argnums=(0,))
    state, _ = f(_state(4), jnp.ones((4,), jnp.float32))
    state, _ = f(state, jnp.ones((4,), jnp.float32))
    small = f.compiled
    grown = _state(8)
    out, _ = f(grown, jnp.ones((8,), jnp.float32))
    assert f.compiled is not small and out["w"].shape == (8,)
    assert grown["w"].is_deleted()           # the new executable's donation
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.grown") == 2
    assert _site_value(reg, "xla_recompiles_total", "unit.grown") == 1
    assert "['w']: ((4,), 'float32', False) -> ((8,), 'float32', False)" \
        in warned[0]


def test_signatures_that_alternate_reuse_the_handles_made_for_them():
    """``jax.jit`` keeps every executable it made; so does the site: a
    signature that comes back (a curriculum's buckets, eval shapes) runs
    the executable made for it, and nothing is compiled."""
    reg = Registry()
    f = _staged(reg, "unit.buckets")
    a, b = jnp.ones((4,), jnp.float32), jnp.ones((8,), jnp.float32)
    f(_state(4), a)
    handle_a = f.compiled
    f(_state(8), b)
    handle_b = f.compiled
    before = _executables()
    for _ in range(3):
        f(_state(4), a)
        assert f.compiled is handle_a
        f(_state(8), b)
        assert f.compiled is handle_b
    assert _executables() == before
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.buckets") == 2


def test_a_refused_sharding_falls_back_without_raising():
    """The kept executable refuses arguments sharded otherwise
    (``ValueError`` from its own check, nothing donated); the site makes
    one for them: warm-up churn before the site has settled, a recompile
    after, as a plain ``jax.jit`` under the watchdog."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("d",))
    x = np.arange(len(jax.devices()) * 2, dtype=np.float32)
    replicated = jax.device_put(x, NamedSharding(mesh, P()))
    sharded = jax.device_put(x, NamedSharding(mesh, P("d")))
    reg = Registry()
    f = recompile.RecompileWatchdog(registry=reg).watch(
        jax.jit(lambda v: v + 1), "unit.resharded", staged=True)
    np.testing.assert_array_equal(f(replicated), x + 1)
    first = f.compiled
    with pytest.raises(ValueError):
        first(sharded)
    np.testing.assert_array_equal(f(sharded), x + 1)   # churn: not settled
    assert f.compiled is not first
    assert _site_value(reg, "xla_recompiles_total", "unit.resharded") == 0
    np.testing.assert_array_equal(f(sharded), x + 1)   # settles
    np.testing.assert_array_equal(f(replicated), x + 1)
    assert f.compiled is first                         # found, not made
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.resharded") == 1


def test_the_steady_call_signs_nothing(monkeypatch):
    """No flatten, hash or signature on the dispatch path, however many
    leaves the state has: only a call the kept executable refuses is
    signed."""
    signed = []
    real_sig = recompile._tree_sig
    monkeypatch.setattr(recompile, "_tree_sig",
                        lambda tree: signed.append(1) or real_sig(tree))
    tree = {f"p{i}": jnp.float32(i) for i in range(3000)}
    f = recompile.RecompileWatchdog(registry=Registry()).watch(
        jax.jit(lambda t, x: x + sum(t[k] for k in ("p0", "p2999"))),
        "unit.steady", staged=True)
    assert float(f(tree, jnp.float32(1))) == 3000.0
    assert len(signed) == 1                  # the call that made it
    for i in range(5):
        f(tree, jnp.float32(i))
    assert len(signed) == 1
    f(tree, jnp.ones((2,), jnp.float32))     # refused: signed once more
    assert len(signed) == 2


def test_a_call_under_someone_elses_trace_inlines_the_jit():
    """The flops profiler lowers a function that calls the step: tracers
    reach the site, which the kept executable refuses; the jit handles
    them, and no handle is made or replaced."""
    f = recompile.RecompileWatchdog(registry=Registry()).watch(
        jax.jit(lambda x: x * 2), "unit.traced", staged=True)
    outer = jax.jit(lambda x: f(x) + 1)
    assert float(outer(jnp.float32(3))) == 7.0
    assert f.compiled is None
    f(jnp.float32(1))
    handle = f.compiled
    assert float(outer(jnp.float32(4))) == 9.0
    assert "multiply" in outer.lower(jnp.float32(1)).as_text()
    assert f.compiled is handle


def test_an_executable_is_booked_when_it_is_made():
    reg = Registry()
    f = _staged(reg, "unit.booked", donate_argnums=(0,))
    f(_state(64), jnp.ones((64,), jnp.float32))
    bd = memory.memory_breakdown(f.compiled)
    assert bd["alias"] > 0                   # the donated state
    assert bd["reserved"] == \
        bd["args"] + bd["output"] - bd["alias"] + bd["temp"]
    assert bd["total"] == bd["reserved"] + bd["alias"]
    for key in ("args", "output", "alias", "temp", "reserved", "total"):
        gauge = reg.gauge(f"hbm_exec_{key}_bytes", labelnames=("site",))
        assert gauge.labels(site="unit.booked").value == bd[key], key


def test_the_default_path_keeps_no_handle():
    f = recompile.watch(jax.jit(lambda x: x + 1), "unit.plain")
    f(jnp.float32(1))
    assert getattr(f, "compiled", None) is None


# ----------------------------------------------------------------------
# the engine's readers
# ----------------------------------------------------------------------
@pytest.fixture
def engine():
    import deepspeed_tpu
    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    mesh_mod.set_mesh(None)
    cfg = gpt2_config("gpt2-tiny", dtype=jnp.float32, scan_layers=False)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg),
        config={"train_micro_batch_size_per_gpu": 1,
                "zero_optimization": {"stage": 3},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "mesh": {"fsdp": -1}, "steps_per_print": 10**9})
    eng.init_params()
    yield eng
    mesh_mod.set_mesh(None)


def _batch(engine):
    ids = np.zeros((engine.train_batch_size, 16), np.int32)
    return {"input_ids": ids, "labels": ids}


def _gauge(name, site="engine.train_step"):
    entry = get_registry().snapshot().get(name)
    return next(s["value"] for s in entry["samples"]
                if s["labels"]["site"] == site)


def test_one_train_batch_books_the_step_that_runs(engine):
    assert engine.compiled_step() is None
    assert engine.record_memory_profile() is None
    engine.train_batch(_batch(engine))
    ma = engine.compiled_step().memory_analysis()
    reserved = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert ma.alias_size_in_bytes > 0        # the step donates its state
    assert _gauge("hbm_exec_reserved_bytes") == reserved
    assert _gauge("hbm_exec_temp_bytes") == ma.temp_size_in_bytes
    assert _gauge("hbm_exec_alias_bytes") == ma.alias_size_in_bytes


def test_record_memory_profile_reads_the_handle_and_compiles_nothing(engine):
    for _ in range(2):
        engine.train_batch(_batch(engine))
    handle = engine.compiled_step()
    before = _executables()
    bd = engine.record_memory_profile()
    assert _executables() == before
    assert engine.compiled_step() is handle
    assert bd == memory.memory_breakdown(handle)
    assert bd["reserved"] == _gauge("hbm_exec_reserved_bytes")


def test_the_train_step_is_made_once_and_every_site_has_its_handle(engine):
    before = _executables()
    batch = _batch(engine)
    for _ in range(3):
        engine.train_batch(batch)
    made = _executables() - before
    handle = engine.compiled_step("engine.train_step")
    engine.train_batch(batch)
    assert _executables() - before == made and \
        engine.compiled_step() is handle
    assert engine.compiled_step("engine.eval_step") is None
    engine.eval_batch(batch)
    assert isinstance(engine.compiled_step("engine.eval_step"),
                      jax.stages.Compiled)
    assert engine.compiled_step("engine.multi_step[2]") is None
    engine.train_batches(batch, steps=2, stacked=False)
    assert "scan" in engine.compiled_step("engine.multi_step[2]").as_text() \
        or "while" in engine.compiled_step("engine.multi_step[2]").as_text()
    engine.reseed(7)                         # drops the step closures
    assert engine.compiled_step() is None


def test_a_prepared_executable_is_what_the_first_call_runs():
    """``prepare`` (PR 62) makes the executable ahead of the call, from
    shapes alone and on another thread; the first call it accepts runs it,
    makes none of its own and books it as that call's compile; arguments it
    refuses cost their own compile and leave it waiting."""
    import threading

    reg = Registry()
    f = _staged(reg, "unit.prepared", donate_argnums=(0,))
    shapes = ({"w": jax.ShapeDtypeStruct((4,), jnp.float32),
               "n": jax.ShapeDtypeStruct((), jnp.int32)},
              jax.ShapeDtypeStruct((4,), jnp.float32))
    thread = threading.Thread(target=f.prepare, args=shapes)
    thread.start()
    thread.join()
    assert len(f._prepared) == 1 and f.compiled is None
    ready = f._prepared[0]
    batch = jnp.ones((4,), jnp.float32)
    other = f(_state(8), jnp.ones((8,), jnp.float32))      # refused by it
    assert f.compiled is not ready and len(f._prepared) == 1
    assert int(other[0]["n"]) == 1
    before = _executables()
    state, metrics = f(_state(), batch)
    assert f.compiled is ready and f._prepared == []
    assert _executables() == before                 # nothing was made
    assert float(state["w"][0]) == 4.0 and float(metrics["loss"]) == 1.0
    assert _site_value(reg, "xla_compiled_signatures_total",
                       "unit.prepared") == 2
    assert reg.gauge("hbm_exec_reserved_bytes", labelnames=("site",)).labels(
        site="unit.prepared").value > 0
    state, _ = f(state, batch)                      # the steady call
    assert f.compiled is ready and int(state["n"]) == 2


def test_the_engine_prepares_its_train_step_beside_init_params():
    """``Engine.prepare_train_step``: the step's executable made from the
    batch's shapes on a thread of the engine's while ``init_params`` runs;
    the first ``train_batch`` runs it (no executable of its own) and the
    loss falls as in a run that compiled in place."""
    import deepspeed_tpu
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    def engine_of():
        cfg = LlamaConfig(vocab_size=160, hidden_size=64, num_hidden_layers=2,
                          num_attention_heads=4, intermediate_size=112,
                          max_position_embeddings=32, scan_layers=False,
                          attn_impl="jnp", vocab_pad_multiple=32)
        return deepspeed_tpu.initialize(model=LlamaForCausalLM(cfg), config={
            "optimizer": {"type": "adamw8bit", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 3}, "mesh": {"fsdp": -1},
            "train_micro_batch_size_per_gpu": 1, "steps_per_print": 10**9})[0]

    engine = engine_of()
    rows = engine.train_batch_size
    ids = np.random.default_rng(1).integers(0, 160, (rows, 32)).astype(
        np.int32)
    batch = {"input_ids": ids, "labels": ids}
    thread = engine.prepare_train_step({
        k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()})
    engine.init_params()
    thread.join()
    site = engine._compiled_train_step
    assert len(site._prepared) == 1
    ready = site._prepared[0]
    losses = [float(engine.train_batch(data_iter=iter([batch] * 3)))
              for _ in range(3)]
    assert site.compiled is ready and site._prepared == []
    assert engine.compiled_step() is ready
    plain = engine_of()
    plain.init_params()
    want = [float(plain.train_batch(data_iter=iter([batch] * 3)))
            for _ in range(3)]
    assert losses == want and losses[-1] < losses[0]
