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


def test_fused_adam8bit_matches_unfused_single_step():
    """ops/pallas/adam8bit_kernel.py fused apply == the optax chain,
    bit-exact on one step (clip + decoupled decay included)."""
    from deepspeed_tpu.ops.adam8bit import _find_state, fused_apply_factory

    rng = np.random.default_rng(1)
    params = {"a": jnp.asarray(rng.normal(size=(40, 96)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(96,)), jnp.float32)}
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32) * 0.1,
        params)

    def sched(c):
        return 1e-3 * (1.0 + c.astype(jnp.float32))

    tx = optax.chain(optax.clip_by_global_norm(0.5),
                     adamw_8bit(sched, weight_decay=0.1))
    state = tx.init(params)
    u, state = tx.update(grads, state, params)     # warm: nonzero moments
    params = optax.apply_updates(params, u)

    u2, state_ref = tx.update(grads, state, params)
    p_ref = optax.apply_updates(params, u2)
    fused = fused_apply_factory(learning_rate=sched, b1=0.9, b2=0.999,
                                eps=1e-8, weight_decay=0.1, clip=0.5)
    p_fused, state_fused = jax.jit(fused)(
        grads, params, state, optax.global_norm(grads))

    # one-ulp FMA/fusion differences between the two compiled programs are
    # expected; a boundary-straddling round can move a code by one level
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                atol=1e-6, rtol=1e-6),
        p_ref, p_fused)
    s_ref, s_f = _find_state(state_ref), _find_state(state_fused)
    assert int(s_f.count) == int(s_ref.count)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_less(
            np.abs(np.asarray(a, np.int32) - np.asarray(b, np.int32)), 2),
        (s_ref.m_codes, s_ref.r_codes), (s_f.m_codes, s_f.r_codes))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-5),
        s_ref.scales, s_f.scales)


def test_fused_adam8bit_engine_single_device(tmp_path):
    """On a 1-device mesh the engine takes the fused path (interpret mode
    on CPU) and the checkpoint layout stays the stock optax chain state."""
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    mesh = mesh_mod.build_mesh(devices=jax.devices()[:1])
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "optimizer": {"type": "adamw8bit",
                         "params": {"lr": 1e-3, "weight_decay": 0.01,
                                    "fused": True}},
           "gradient_clipping": 1.0,
           "zero_optimization": {"stage": 1}}
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", scan_layers=True))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg,
                                               mesh=mesh)
    assert engine._fused_opt is not None
    engine.init_params()
    batch = token_batch(engine.train_batch_size, 32, 512)
    losses = [float(engine.train_batch(batch)) for _ in range(6)]
    assert losses[-1] < losses[0]
    engine.save_checkpoint(str(tmp_path), tag="fq8")
    # resume into an engine with the fused path disabled: same state tree
    mesh_mod.set_mesh(None)
    cfg2 = {**cfg, "optimizer": {"type": "adamw8bit",
                                 "params": {"lr": 1e-3, "weight_decay": 0.01,
                                            "fused": False}}}
    mesh2 = mesh_mod.build_mesh(devices=jax.devices()[:1])
    engine2, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(gpt2_config("gpt2-tiny", scan_layers=True)),
        config=cfg2, mesh=mesh2)
    assert engine2._fused_opt is None
    engine2.init_params()
    engine2.load_checkpoint(str(tmp_path), tag="fq8")
    l2 = float(engine2.train_batch(batch))
    assert np.isfinite(l2) and l2 < losses[0]


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
    engine2.load_checkpoint(str(tmp_path), tag="q8")
    l2 = [float(engine2.train_batch(batch)) for _ in range(2)]
    l1 = [float(engine.train_batch(batch)) for _ in range(2)]
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
