"""Engine end-to-end on the 8-device CPU mesh — the analog of reference
``tests/unit/test_fp16.py`` / ``test_ds_initialize.py`` training smokes."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod

from .simple_model import SimpleModel, random_dataset, token_batch


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def make_engine(config=None, model=None, **kw):
    config = config or {}
    config.setdefault("train_micro_batch_size_per_gpu", 2)
    config.setdefault("optimizer", {"type": "Adam", "params": {"lr": 1e-2}})
    model = model or SimpleModel()
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config, **kw)
    engine.init_params()
    return engine


def batch_for(engine, seed=0):
    rng = np.random.default_rng(seed)
    b = engine.train_batch_size
    x = rng.normal(size=(b, 16)).astype(np.float32)
    return {"x": x, "y": 0.1 * x}


def test_train_loss_decreases():
    engine = make_engine()
    losses = [float(engine.train_batch(batch_for(engine, seed=i))) for i in range(20)]
    assert losses[-1] < losses[0] * 0.5


def test_gradient_accumulation_equivalence():
    """gas=2 over a batch must equal gas=1 over the same concatenated batch."""
    cfg1 = {"train_micro_batch_size_per_gpu": 4, "gradient_accumulation_steps": 1,
            "optimizer": {"type": "sgd", "params": {"lr": 0.1}}}
    cfg2 = {"train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "sgd", "params": {"lr": 0.1}}}
    e1 = make_engine(cfg1)
    mesh_mod.set_mesh(None)
    e2 = make_engine(cfg2)
    assert e1.train_batch_size == e2.train_batch_size == 32
    batch = batch_for(e1, seed=3)
    e1.train_batch(batch)
    # rank-major relayout: e2 scans micro-batches; feed the same rows
    dpw, gas = e2.dp_world, 2
    def relayout(x):
        y = x.reshape(gas, dpw, -1, *x.shape[1:])
        return y.transpose(1, 0, 2, *range(3, y.ndim)).reshape(x.shape)
    e2.train_batch({k: relayout(v) for k, v in batch.items()})
    p1 = jax.device_get(e1.params)
    p2 = jax.device_get(e2.params)
    flat1 = jax.tree_util.tree_leaves(p1)
    flat2 = jax.tree_util.tree_leaves(p2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stages_agree(stage):
    """All ZeRO stages are the same math, different placement."""
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
           "zero_optimization": {"stage": stage}}
    engine = make_engine(cfg)
    batch = batch_for(engine, seed=7)
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    if stage == 0:
        pytest.shared_losses = losses
    else:
        ref = getattr(pytest, "shared_losses", None)
        if ref is not None:
            np.testing.assert_allclose(losses, ref, rtol=1e-4)


def test_zero3_shards_params():
    cfg = {"train_micro_batch_size_per_gpu": 2, "zero_optimization": {"stage": 3},
           "optimizer": {"type": "adam", "params": {"lr": 1e-3}}}
    engine = make_engine(cfg)
    assert engine.mesh.shape["fsdp"] == 8  # dp promoted to fsdp
    kernel = engine.params["linear_0"]["kernel"]
    assert "fsdp" in str(kernel.sharding.spec)


def test_zero1_shards_opt_state_only():
    cfg = {"train_micro_batch_size_per_gpu": 2, "zero_optimization": {"stage": 1},
           "optimizer": {"type": "adam", "params": {"lr": 1e-3}}}
    engine = make_engine(cfg)
    # params replicated
    kernel = engine.params["linear_0"]["kernel"]
    assert kernel.sharding.spec == jax.sharding.PartitionSpec(None, None) or \
        kernel.sharding.spec == jax.sharding.PartitionSpec()
    # adam mu sharded over fsdp
    mu_leaves = jax.tree_util.tree_leaves(engine.state.opt_state)
    assert any("fsdp" in str(l.sharding.spec) for l in mu_leaves if hasattr(l, "sharding"))


def test_forward_backward_step_compat_matches_train_batch():
    cfg = {"train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 2,
           "optimizer": {"type": "sgd", "params": {"lr": 0.1}}}
    e1 = make_engine(cfg)
    mesh_mod.set_mesh(None)
    e2 = make_engine(cfg)
    batch = batch_for(e1, seed=5)  # (32, ...) = gas(2) × micro(2) × dp(8)
    e1.train_batch(batch)

    # compat path: feed the two micro-batches (rank-major layout rows)
    dpw, gas, micro = e2.dp_world, 2, 2
    def micro_slice(x, g):
        xs = x.reshape(dpw, gas, micro, *x.shape[1:])
        return xs[:, g].reshape(dpw * micro, *x.shape[1:])
    for g in range(gas):
        mb = {k: micro_slice(v, g) for k, v in batch.items()}
        loss = e2(mb)
        e2.backward(loss)
        e2.step()
    assert e2.global_steps == 1
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(e1.params)),
                    jax.tree_util.tree_leaves(jax.device_get(e2.params))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_fp16_loss_scaling_runs():
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "fp16": {"enabled": True, "initial_scale_power": 8},
           "optimizer": {"type": "adam", "params": {"lr": 1e-3}}}
    engine = make_engine(cfg)
    batch = batch_for(engine)
    for _ in range(3):
        loss = engine.train_batch(batch)
    assert np.isfinite(float(loss))
    assert float(engine.state.loss_scale.scale) == 2 ** 8


def test_gpt2_tiny_trains():
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", scan_layers=True, remat=True))
    cfg = {"train_micro_batch_size_per_gpu": 1,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "gradient_clipping": 1.0,
           "zero_optimization": {"stage": 3}}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    engine.init_params()
    batch = token_batch(engine.train_batch_size, 32, 512)
    losses = [float(engine.train_batch(batch)) for _ in range(8)]
    assert losses[-1] < losses[0]  # memorizing a fixed batch


def test_dataloader_train_batch_from_iterator():
    from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader

    cfg = {"train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 2,
           "optimizer": {"type": "adam", "params": {"lr": 1e-2}}}
    data = random_dataset(256, 16)
    model = SimpleModel()
    engine, _, loader, _ = deepspeed_tpu.initialize(
        model=model, config=cfg, training_data=data)
    engine.init_params()
    assert isinstance(loader, DeepSpeedDataLoader)
    assert loader.batch_size == 16  # micro(2) × dp(8)
    loss = engine.train_batch()
    assert np.isfinite(float(loss))
    assert engine.global_samples == 32


# ---------------- sparse gradients (reference engine.py:2182) ----------------

def _embed_engine(sparse: bool, gas: int = 1):
    from .simple_model import EmbedModel

    cfg = {"train_micro_batch_size_per_gpu": 2,
           "gradient_accumulation_steps": gas,
           "optimizer": {"type": "adamw", "params": {"lr": 5e-2}},
           "zero_optimization": {"stage": 1}}
    if sparse:
        cfg["sparse_gradients"] = True
        cfg["sparse_gradient_modules"] = ["tok_embed"]
    engine, _, _, _ = deepspeed_tpu.initialize(model=EmbedModel(), config=cfg)
    engine.init_params()
    return engine


@pytest.mark.parametrize("gas", [1, 2])
def test_sparse_gradients_match_dense(gas):
    """Row-sparse embedding allreduce is EXACT: same losses and params as
    the dense reduction (capacity = token count ≥ touched rows)."""
    mesh_mod.set_mesh(None)
    dense = _embed_engine(sparse=False, gas=gas)
    batches = [token_batch(dense.train_batch_size, 8, 64, seed=i)
               for i in range(3)]
    dense_losses = [float(dense.train_batch(b)) for b in batches]
    dense_params = jax.device_get(dense.params)

    mesh_mod.set_mesh(None)
    sparse = _embed_engine(sparse=True, gas=gas)
    sparse_losses = [float(sparse.train_batch(b)) for b in batches]
    sparse_params = jax.device_get(sparse.params)

    np.testing.assert_allclose(sparse_losses, dense_losses, rtol=2e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6),
        dense_params, sparse_params)


def test_sparse_gradients_requires_module_list():
    from .simple_model import EmbedModel

    with pytest.raises(ValueError, match="sparse_gradient_modules"):
        deepspeed_tpu.initialize(model=EmbedModel(), config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "sparse_gradients": True})


def test_sparse_gradients_rejects_sharded_params():
    from .simple_model import EmbedModel

    with pytest.raises(NotImplementedError):
        deepspeed_tpu.initialize(model=EmbedModel(), config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "sparse_gradients": True,
            "sparse_gradient_modules": ["tok_embed"],
            "zero_optimization": {"stage": 3}})


def test_chunked_lm_loss_matches_dense():
    """cfg.loss_chunk computes the same loss/grads as the dense head
    without materializing (B,S,V) logits (float-reassociation noise only)."""
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    ids = np.random.default_rng(0).integers(0, 512, size=(2, 32)).astype(np.int32)

    def loss_and_gradsum(chunk):
        cfg = gpt2_config("gpt2-tiny", scan_layers=True, loss_chunk=chunk)
        m = GPT2LMHeadModel(cfg)
        params = jax.jit(m.init)(jax.random.PRNGKey(0), ids)["params"]
        loss, g = jax.jit(jax.value_and_grad(lambda p: m.apply(
            {"params": p}, ids, labels=ids)["loss"]))(params)
        gsum = jax.tree_util.tree_reduce(
            lambda a, b: a + float(jnp.sum(jnp.abs(b))), g, 0.0)
        return float(loss), float(gsum)

    l0, g0 = loss_and_gradsum(None)
    l1, g1 = loss_and_gradsum(16)   # 64 rows -> 4 chunks
    assert abs(l1 - l0) / abs(l0) < 1e-4
    # one bf16 step: the dense head rounds dlog after the 1 / count scale,
    # the chunked one before it (its backward scales the finished products)
    assert abs(g1 - g0) / g0 < 2 ** -8


@pytest.mark.parametrize("scale", ["constant", "traced", "2^-15"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk", [8, 32])
def test_chunked_lm_loss_makes_its_cotangents_in_the_forward(chunk, dtype,
                                                             scale):
    """The custom-vjp head, whose forward rule makes ``dh`` and ``dW`` and
    whose backward scales them: 4 chunks of 8 and one whole chunk, a padded
    vocabulary, an ignored label, the loss scaled by a constant, by a traced
    scalar and so that the head's cotangent is a power of two.  float32
    operands against the dense ``cross_entropy_loss``; bf16 operands against
    the formula the backward ran before (the scale applied before ``dlog``
    is rounded, ``dh`` out of its product in bf16): within one bf16 step,
    and equal where the cotangent is a power of two."""
    from deepspeed_tpu.models.common import chunked_lm_loss, \
        cross_entropy_loss

    rng = np.random.default_rng(3)
    B, S, E, V, Vp = 2, 16, 32, 101, 128
    h = jnp.asarray(rng.normal(size=(B, S, E)), dtype)
    wte = jnp.asarray(rng.normal(size=(Vp, E)), dtype)
    lbl = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)
    lbl = lbl.at[0, 3].set(-100)
    # "2^-15" is the head's cotangent: the scale times 1 / (31 valid labels)
    c = jnp.float32({"constant": 3.0, "traced": 0.37, "2^-15": 31 * 2.0 ** -15}
                    [scale])

    def logits_of(h, wte):
        logits = jnp.dot(h, wte.T, preferred_element_type=jnp.float32)
        return jnp.where(jnp.arange(Vp) < V, logits,
                         jnp.finfo(jnp.float32).min)

    def dense(h, wte, c):
        return c * cross_entropy_loss(logits_of(h, wte), lbl)

    def fused(h, wte, c):
        return c * chunked_lm_loss(h, wte, lbl, vocab_size=V,
                                   padded_vocab_size=Vp, chunk=chunk,
                                   dtype=dtype)

    if scale == "traced":
        fused, dense = jax.jit(fused), jax.jit(dense)
    l0, (gh0, gw0) = jax.value_and_grad(dense, (0, 1))(h, wte, c)
    l1, (gh1, gw1) = jax.value_and_grad(fused, (0, 1))(h, wte, c)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    np.testing.assert_allclose(float(fused(h, wte, c)), float(l1), rtol=1e-6)
    assert gh1.dtype == gw1.dtype == dtype
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(gh0), np.asarray(gh1),
                                   atol=1e-6 * max(float(c), 1.0))
        np.testing.assert_allclose(np.asarray(gw0), np.asarray(gw1),
                                   atol=1e-6 * max(float(c), 1.0))
        return
    # the parent's formula, chunk by chunk, on the same bf16 operands
    valid = (lbl != -100).reshape(-1)
    g = c / valid.sum()
    logits = logits_of(h.reshape(-1, E), wte)
    p = jax.nn.softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(jnp.where(valid, lbl.reshape(-1), 0), Vp)
    dlog = ((p - onehot) * (g * valid)[:, None]).astype(dtype)
    want_h = jnp.dot(dlog, wte).reshape(B, S, E)
    want_w = sum(jnp.dot(dlog[i:i + chunk].T, h.reshape(-1, E)[i:i + chunk],
                         preferred_element_type=jnp.float32)
                 for i in range(0, B * S, chunk)).astype(dtype)
    for got, want in ((gh1, want_h), (gw1, want_w)):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        if scale == "2^-15":
            np.testing.assert_array_equal(got, want)
        else:
            # one bf16 step of the larger of the two (8 bits of mantissa),
            # and the rounding of dlog itself summed over a row
            step = np.maximum(np.abs(got), np.abs(want)) * 2.0 ** -7
            assert (np.abs(got - want) <= step + np.abs(want).max() * 2e-3
                    ).all()


def test_train_batches_matches_per_step_calls():
    """train_batches (one compiled scan) == N train_batch calls: same
    losses, same final params; stacked per-step batches also work."""
    import deepspeed_tpu
    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    cfg = {"train_micro_batch_size_per_gpu": 1,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "gradient_clipping": 1.0,
           "zero_optimization": {"stage": 1}}

    def fresh():
        mesh_mod.set_mesh(None)
        m = GPT2LMHeadModel(gpt2_config("gpt2-tiny", scan_layers=True))
        e, _, _, _ = deepspeed_tpu.initialize(model=m, config=cfg)
        e.init_params()
        return e

    e1 = fresh()
    ids = np.random.default_rng(0).integers(
        0, 512, size=(e1.train_batch_size, 32)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    l_ref = [float(e1.train_batch(batch)) for _ in range(4)]

    e2 = fresh()
    l_multi = np.asarray(jax.device_get(e2.train_batches(batch, steps=4)))
    np.testing.assert_allclose(l_multi, l_ref, rtol=2e-4, atol=1e-6)
    assert e2.global_steps == 4
    # (param-level equality is not asserted: the scan and the single-step
    # programs fuse differently, and 1e-4-level loss diffs pass through
    # Adam's m/sqrt(v) normalization into ~1e-5 param deltas)

    # stacked per-step batches: different data each step
    e3 = fresh()
    rngs = np.random.default_rng(1)
    stack = rngs.integers(0, 512, size=(3, e3.train_batch_size, 32)).astype(np.int32)
    l_stacked = e3.train_batches({"input_ids": stack, "labels": stack}, steps=3)
    e4 = fresh()
    l_per = [float(e4.train_batch({"input_ids": stack[i], "labels": stack[i]}))
             for i in range(3)]
    np.testing.assert_allclose(np.asarray(jax.device_get(l_stacked)), l_per,
                               rtol=2e-4, atol=1e-6)


def test_grad_accum_dtype_bf16():
    """data_types.grad_accum_dtype=bf16 (reference parity knob): grads are
    produced/accumulated in bf16, training stays sane vs fp32 grads."""
    def run(dtype):
        mesh_mod.set_mesh(None)
        cfg = {"train_micro_batch_size_per_gpu": 2,
               "gradient_accumulation_steps": 2,
               "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
               "data_types": {"grad_accum_dtype": dtype}}
        e = make_engine(cfg)
        return [float(e.train_batch(batch_for(e, seed=3))) for _ in range(6)]

    l32 = run("fp32")
    l16 = run("bf16")
    assert l16[-1] < l16[0] * 0.8
    np.testing.assert_allclose(l16, l32, rtol=0.05)

    from deepspeed_tpu.runtime.config import Config, ConfigError
    with pytest.raises(ConfigError):
        Config.from_dict({"train_micro_batch_size_per_gpu": 1,
                          "data_types": {"grad_accum_dtype": "int8"}})
    with pytest.raises(ConfigError):
        Config.from_dict({"train_micro_batch_size_per_gpu": 1,
                          "fp16": {"enabled": True},
                          "data_types": {"grad_accum_dtype": "bf16"}})


# ----------------------------------------------------------------------
# every step program carries one observer (telemetry/recompile.py)
# ----------------------------------------------------------------------
def _tiny_lm_engine(optimizer):
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    return make_engine({"train_micro_batch_size_per_gpu": 1,
                        "optimizer": optimizer,
                        "zero_optimization": {"stage": 0},
                        "mesh": {"dp": 8}, "steps_per_print": 10**6},
                       model=GPT2LMHeadModel(
                           gpt2_config("gpt2-tiny", scan_layers=True)))


def _three_call(engine, batch):
    engine.forward(batch)
    engine.backward()
    engine.step()


def _train_then_eval(engine, batch):
    engine.train_batch(batch)       # eval reads a jit output's params
    engine.eval_batch(batch)


# name -> (engine, one step, the sites the step calls)
_STEP_PROGRAMS = {
    "train_step": (make_engine, lambda e, b: e.train_batch(b),
                   ["engine.train_step"]),
    "multi_step_window": (
        make_engine, lambda e, b: e.train_batches(b, 2, stacked=False),
        ["engine.multi_step[2]"]),
    "eval": (make_engine, _train_then_eval,
             ["engine.train_step", "engine.eval_step"]),
    "grad_and_apply": (make_engine, _three_call,
                       ["engine.grad_step", "engine.apply_step"]),
    "host_offload_grads": (
        lambda: make_engine({
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 2, "offload_optimizer":
                                  {"device": "cpu"}}}),
        lambda e, b: e.train_batch(b), ["engine.grads_only"]),
    "onebit": (
        lambda: _tiny_lm_engine({"type": "onebitadam", "params": {
            "lr": 1e-3, "freeze_step": 1, "comm_backend": "compressed"}}),
        lambda e, b: e.train_batch(b), ["engine.train_step"]),
}


@pytest.mark.parametrize("program", list(_STEP_PROGRAMS))
def test_step_program_compiles_once_on_a_donated_state(program):
    """Three steps of each step program, the state donated and replaced
    every step: its sites count one warm-up compile and no recompile,
    and after the first step no span of the step makes an executable."""
    from deepspeed_tpu.telemetry import registry as telemetry_registry

    def by(metric, label, keep):
        snap = telemetry_registry.get_registry().snapshot().get(metric)
        return {} if snap is None else {
            s["labels"][label]: s["value"] for s in snap["samples"]
            if keep(s["labels"][label])}

    def made_in_step_spans():
        return sum(by("xla_executables_total", "span", lambda span: span in (
            "train/dispatch", "train/apply-step", "eval/dispatch")).values())

    build, step, sites = _STEP_PROGRAMS[program]
    compiled0 = by("xla_compiled_signatures_total", "site", sites.__contains__)
    recompiled0 = by("xla_recompiles_total", "site", sites.__contains__)
    engine = build()
    batch = token_batch(engine.train_batch_size, 32, 512, seed=0) \
        if program == "onebit" else batch_for(engine)
    step(engine, batch)
    made = made_in_step_spans()
    assert made > 0                 # the first step compiled in its spans
    step(engine, batch)
    step(engine, batch)
    assert made_in_step_spans() == made
    compiled = by("xla_compiled_signatures_total", "site", sites.__contains__)
    assert {s: compiled[s] - compiled0.get(s, 0) for s in sites} \
        == dict.fromkeys(sites, 1)
    assert by("xla_recompiles_total", "site", sites.__contains__) \
        == recompiled0
