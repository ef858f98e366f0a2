"""8-bit Adam state tests — the quantized-state family (ops/adam8bit.py;
reference compressed-state precedent ``runtime/fp16/onebit/``)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.ops.adam8bit import adamw_8bit

from .simple_model import SimpleModel, token_batch


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def _rosenbrockish_losses(tx, steps=60):
    """Optimize a small quadratic-ish problem; return the loss trace."""
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.normal(size=(24, 24)), jnp.float32) / 5.0
    b = jnp.asarray(rng.normal(size=(24,)), jnp.float32)
    params = {"w": jnp.zeros((24, 24)), "c": jnp.zeros((24,))}

    def loss_fn(p):
        r = p["w"] @ b + p["c"] - A @ b
        return jnp.sum(r * r) + 0.1 * jnp.sum((p["w"] - A) ** 2)

    state = tx.init(params)

    @jax.jit
    def step(params, state):
        loss, g = jax.value_and_grad(loss_fn)(params)
        upd, state = tx.update(g, state, params)
        return optax.apply_updates(params, upd), state, loss

    trace = []
    for _ in range(steps):
        params, state, loss = step(params, state)
        trace.append(float(loss))
    return trace


def test_adam8bit_tracks_fp32_adam():
    ref = _rosenbrockish_losses(optax.adamw(5e-2))
    q8 = _rosenbrockish_losses(adamw_8bit(5e-2))
    assert q8[-1] < ref[0] * 0.05          # converges
    # quantization noise stays small relative to progress
    assert q8[-1] < ref[-1] * 3 + 1e-3


def test_adam8bit_update_stays_bounded_in_rows_of_mixed_scales():
    """Rows whose columns differ by orders of magnitude, a column's
    gradient present or exactly zero (an (embed, vocab) head under Zipf
    token frequencies).  Adam's update is bounded by |m| <= 7.3 sqrt(v);
    with sqrt(v) rounded to NEAREST, small columns stored a zero
    denominator beside a live first moment and this very stream read an
    update of 2.2e9 (and OLMoE diverged on the v5e, PR 26).  Rounded up,
    the worst update here is 1.13."""
    from deepspeed_tpu.ops.adam8bit import _quant_pos, scale_by_adam8bit

    rng = np.random.default_rng(0)
    x = jnp.asarray(np.exp(rng.normal(0, 3.0, size=(8, 512))), jnp.float32)
    codes, scale = _quant_pos(x)
    assert bool((codes.astype(jnp.float32) * scale >= x * (1 - 1e-6)).all())

    tx = scale_by_adam8bit()
    params = {"w": jnp.zeros((4, 512))}
    state = tx.init(params)
    step = jax.jit(lambda g, st: tx.update({"w": g}, st, params))
    scales = np.exp(rng.normal(0, 3.0, size=(1, 512)))
    worst = 0.0
    for _ in range(200):
        present = rng.random((4, 512)) < 0.5
        g = jnp.asarray(rng.normal(size=(4, 512)) * scales * present,
                        jnp.float32)
        upd, state = step(g, state)
        worst = max(worst, float(jnp.abs(upd["w"]).max()))
    assert worst <= 15.0


def test_adam8bit_state_dtypes_and_memory():
    tx = adamw_8bit(1e-3)
    params = {"k": jnp.zeros((64, 256)), "b": jnp.zeros((256,))}
    state = tx.init(params)
    inner = state[0]  # chain: (scale_by_adam8bit, scale_by_lr)
    assert inner.m_codes["k"].dtype == jnp.int8
    assert inner.r_codes["k"].dtype == jnp.uint8
    assert inner.m_codes["k"].shape == (64, 256)
    assert inner.scales["k"]["m"].shape == (64, 1)
    # 2 bytes/param codes + per-row scales ≪ 8 bytes/param fp32 moments
    nbytes = sum(l.nbytes for l in jax.tree_util.tree_leaves(inner))
    assert nbytes < 0.4 * sum(
        8 * l.size for l in jax.tree_util.tree_leaves(params))


def _leaf(kind, rng):
    """A master leaf and a stream of gradients of one of the test's kinds."""
    shape = {"vector": (1600,), "short": (48, 256), "stacked": (3, 64, 128),
             "stored-transposed": (640, 200), "zero+mixed": (160, 384)}[kind]
    p = rng.normal(size=shape).astype(np.float32)
    # columns of mixed scale: what an (embed, vocab) head's rows look like
    cols = np.exp(rng.normal(0, 2.0, size=shape[-1:]))

    def grad():
        g = rng.normal(size=shape) * cols
        if kind == "zero+mixed":
            g[:32] = 0.0            # rows that never see a gradient
            g[32:64] *= rng.random(g[32:64].shape) < 0.5
        return g

    return p, grad


@pytest.mark.parametrize("clip", [False, True], ids=["noclip", "clip"])
@pytest.mark.parametrize("decay", ["adamw", "l2"])
@pytest.mark.parametrize("gdtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("kind", ["vector", "short", "stacked",
                                  "stored-transposed", "zero+mixed"])
def test_adam8bit_kernel_matches_leaf_moments(kind, gdtype, decay, clip):
    """``ops/pallas/adam8bit_kernel.py`` (interpret mode) against
    ``_leaf_moments`` + decay + lr, three steps chained: every step both
    start from the state the kernel left, so the moments are live and a
    code that flipped a step earlier is not compared twice.

    Tolerances: the kernel folds clip and loss factor into one scalar and
    multiplies by a row's reciprocal where the chain divides, an fp32 ulp
    each.  That moves the master by < 1e-6, an m code by one unit on a
    few entries in 1e5, and an r code by one unit on up to 1.5% of them:
    r is rounded UP, and an entry that sees no gradient decays in step
    with its row's maximum, so ``r / scale`` sits on an integer where an
    ulp decides the ceiling."""
    from deepspeed_tpu.ops.adam8bit import _leaf_moments
    from deepspeed_tpu.ops.pallas.adam8bit_kernel import (apply_leaf,
                                                          stored_transposed)

    rng = np.random.default_rng(3)
    p, grad = _leaf(kind, rng)
    assert stored_transposed(p.shape) == (kind == "stored-transposed")
    sshape = p.shape[:-1] + (1,)
    state = (jnp.asarray(p), jnp.zeros(p.shape, jnp.int8),
             jnp.zeros(p.shape, jnp.uint8),
             {"m": jnp.ones(sshape, jnp.float32),
              "r": jnp.ones(sshape, jnp.float32)})
    b1, b2, eps, lr = 0.9, 0.999, 1e-8, 1e-3
    wd, l2 = (0.1, 0.0) if decay == "adamw" else (0.0, 0.1)
    gscale = 0.37 if clip else 1.0
    for t in (1, 2, 3):
        g = jnp.asarray(grad(), gdtype)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p0, mc0, rc0, sc0 = state
        state = apply_leaf(
            g, p0, mc0, rc0, sc0, jnp.asarray([gscale, lr, c1, c2],
                                              jnp.float32),
            b1=b1, b2=b2, eps=eps, wd=wd, l2=l2, interpret=True)
        gf = g.astype(jnp.float32) * gscale
        if l2:
            gf = gf + l2 * p0
        upd, mc_w, rc_w, sc_w = _leaf_moments(gf, mc0, rc0, sc0, b1=b1, b2=b2,
                                              c1=c1, c2=c2, eps=eps)
        if wd:
            upd = upd + wd * p0
        p_k, mc_k, rc_k, sc_k = state
        np.testing.assert_allclose(p_k, p0 - lr * upd, rtol=1e-6, atol=1e-9)
        for got, want, share in ((mc_k, mc_w, 1e-3), (rc_k, rc_w, 0.03)):
            d = np.abs(np.asarray(got, np.int32) - np.asarray(want, np.int32))
            assert d.max() <= 1 and (d > 0).mean() <= share, (t, d.max(),
                                                              (d > 0).mean())
        assert mc_k.dtype == jnp.int8 and rc_k.dtype == jnp.uint8
        for k in ("m", "r"):
            assert sc_k[k].shape == sshape
            np.testing.assert_allclose(sc_k[k], sc_w[k], rtol=1e-6)
        # a stored denominator is never under the true one
        r0 = rc0.astype(jnp.float32) * sc0["r"]
        root = jnp.sqrt(b2 * r0 * r0 + (1 - b2) * gf * gf)
        assert bool((rc_k.astype(jnp.float32) * sc_k["r"]
                     >= root * (1 - 1e-6)).all())


def test_adam8bit_kernel_path_stays_bounded_in_rows_of_mixed_scales():
    """The bound of ``test_adam8bit_update_stays_bounded_...`` through the
    kernel: mixed-scale rows, gradients present or exactly zero, 60 steps;
    no update beyond Adam's own bound."""
    from deepspeed_tpu.ops.pallas.adam8bit_kernel import apply_leaf

    rng = np.random.default_rng(0)
    shape = (128, 512)
    scales = np.exp(rng.normal(0, 3.0, size=(1, 512)))
    state = (jnp.zeros(shape), jnp.zeros(shape, jnp.int8),
             jnp.zeros(shape, jnp.uint8),
             {"m": jnp.ones((128, 1)), "r": jnp.ones((128, 1))})
    worst = 0.0
    for t in range(1, 61):
        present = rng.random(shape) < 0.5
        g = jnp.asarray(rng.normal(size=shape) * scales * present,
                        jnp.float32)
        before = state[0]
        state = apply_leaf(
            g, *state, jnp.asarray([1.0, 1.0, 1 - 0.9 ** t, 1 - 0.999 ** t],
                                   jnp.float32),
            b1=0.9, b2=0.999, eps=1e-8, wd=0.0, l2=0.0, interpret=True)
        worst = max(worst, float(jnp.abs(state[0] - before).max()))
    assert worst <= 15.0


def _wide_tiny():
    """A GPT-2 small enough for the CPU whose matrices fill a kernel
    block (128 rows of 128 lanes); biases and norms do not."""
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    return GPT2LMHeadModel(gpt2_config("gpt2-tiny", n_embd=128, n_layer=1,
                                       scan_layers=False))


def _adam8bit_counts():
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    return {(impl, reason): n for site, impl, reason, n in dispatch_report()
            if site == "adam8bit"}


_ROUTES = {
    # name: (config overrides, devices, on a TPU?, expected (impl, reason))
    "cpu": ({}, 1, False, ("xla", "not a TPU")),
    "mesh": ({}, 8, True, ("xla", "mesh of 8 devices")),
    "fp16": ({"fp16": {"enabled": True}}, 1, True,
             ("xla", "fp16 overflow skip selects over the state")),
    "tpu": ({"bf16": {"enabled": True}}, 1, True,
            ("kernel", "one device, whole leaves")),
}


@pytest.mark.parametrize("route", sorted(_ROUTES))
def test_adam8bit_route_is_chosen_from_what_the_run_shows(route, monkeypatch):
    """One TPU device holding whole leaves takes the kernel for every leaf
    of a block or more and books the small ones with their reason; a CPU,
    a mesh, fp16's overflow skip keep ``tx.update`` for every leaf.  The
    step is traced, not run (a described TPU is not attached): the
    counter is booked when the step is staged."""
    from deepspeed_tpu.ops import attention

    extra, n_dev, tpu, want = _ROUTES[route]
    monkeypatch.setattr(attention, "on_tpu", lambda: tpu)
    mesh = mesh_mod.build_mesh(devices=jax.devices()[:n_dev])
    cfg = {"train_micro_batch_size_per_gpu": 1,
           "optimizer": {"type": "adamw8bit",
                         # a key from before PR 27: ignored like any other
                         "params": {"lr": 1e-3, "fused": True}},
           "gradient_clipping": 1.0, "zero_optimization": {"stage": 3},
           **extra}
    engine, _, _, _ = deepspeed_tpu.initialize(model=_wide_tiny(), config=cfg,
                                               mesh=mesh)
    batch = token_batch(engine.train_batch_size, 32, 512)
    before = _adam8bit_counts()
    jaxpr = jax.make_jaxpr(engine._train_step_body)(
        engine.abstract_state(batch), batch)
    counts = {k: n - before.get(k, 0)
              for k, n in _adam8bit_counts().items() if n > before.get(k, 0)}
    n_leaves = len(jax.tree_util.tree_leaves(
        engine.abstract_state(batch).params))
    kernels = sum(eqn.params.get("name") == "_leaf_update"
                  for eqn in jaxpr.eqns)
    if want[0] == "kernel":
        # wte, wpe and the block's four matrices; 10 vectors stay behind
        assert counts == {want: 6, ("xla", "leaf under one block"): 10}
        assert kernels == 6
    else:
        assert counts == {want: n_leaves} and kernels == 0


def test_adam8bit_offload_keeps_the_host_optimizer():
    mesh = mesh_mod.build_mesh(devices=jax.devices()[:1])
    cfg = {"train_micro_batch_size_per_gpu": 1,
           "optimizer": {"type": "adamw8bit", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 2,
                                 "offload_optimizer": {"device": "cpu"}}}
    engine, _, _, _ = deepspeed_tpu.initialize(model=_wide_tiny(), config=cfg,
                                               mesh=mesh)
    assert engine._adam8bit_refusal == "optimizer offload"
    assert not engine._adam8bit_kernel


@pytest.mark.parametrize("writer", ["kernel", "xla"])
def test_adam8bit_checkpoint_resumes_across_paths(writer, tmp_path,
                                                  monkeypatch):
    """A checkpoint written by one path loads in the other: the kernel
    path (forced here, interpreted on the CPU) bypasses ``tx.update`` and
    keeps the optax chain's state tree."""
    from deepspeed_tpu.ops import adam8bit as a8

    cfg = {"train_micro_batch_size_per_gpu": 2,
           "optimizer": {"type": "adamw8bit",
                         "params": {"lr": 1e-3, "weight_decay": 0.01}},
           "gradient_clipping": 1.0, "bf16": {"enabled": True},
           "zero_optimization": {"stage": 1}}
    refusal = a8.kernel_refusal

    def engine_on(path):
        mesh_mod.set_mesh(None)
        monkeypatch.setattr(a8, "kernel_refusal", (lambda **kw: None)
                            if path == "kernel" else refusal)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=_wide_tiny(), config=cfg,
            mesh=mesh_mod.build_mesh(devices=jax.devices()[:1]))
        assert engine._adam8bit_kernel == (path == "kernel")
        engine.init_params()
        return engine

    engine = engine_on(writer)
    batch = token_batch(engine.train_batch_size, 32, 512)
    losses = [float(engine.train_batch(batch)) for _ in range(6)]
    assert losses[-1] < losses[0]
    engine.save_checkpoint(str(tmp_path), tag="q8")
    reader = engine_on("xla" if writer == "kernel" else "kernel")
    reader.load_checkpoint(str(tmp_path), tag="q8")
    resumed = float(reader.train_batch(batch))
    assert np.isfinite(resumed) and resumed < losses[-1] + 0.05
    # the same step from the same state in either path, to what one code
    # unit on a few entries moves a loss
    np.testing.assert_allclose(resumed, float(engine.train_batch(batch)),
                               rtol=2e-3)


def test_engine_trains_with_adam8bit_and_checkpoints(tmp_path):
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", scan_layers=True))
    cfg = {"train_micro_batch_size_per_gpu": 1,
           "optimizer": {"type": "adamw8bit",
                         "params": {"lr": 1e-3, "weight_decay": 0.01}},
           "gradient_clipping": 1.0,
           "zero_optimization": {"stage": 1}}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    engine.init_params()
    batch = token_batch(engine.train_batch_size, 32, 512)
    losses = [float(engine.train_batch(batch)) for _ in range(8)]
    assert losses[-1] < losses[0]

    engine.save_checkpoint(str(tmp_path), tag="q8")
    mesh_mod.set_mesh(None)
    engine2, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(gpt2_config("gpt2-tiny", scan_layers=True)),
        config=cfg)
    engine2.init_params()
    # an identical engine runs the step the first one compiled: the state is
    # an argument of it, and a second trace and compile proves nothing here
    engine2.__dict__["_compiled_train_step"] = engine._compiled_train_step
    engine2.load_checkpoint(str(tmp_path), tag="q8")
    l2 = [float(engine2.train_batch(batch)) for _ in range(2)]
    l1 = [float(engine.train_batch(batch)) for _ in range(2)]
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
