"""End-to-end pipeline-parallel GPT-2 through the engine — PP result must
match the non-PP engine on identical data/init (analog of reference
``test_pipe.py``'s train-parity assertions)."""
import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

from .simple_model import seeded_params, token_batch


def _partial_manual_axis_index_lowers() -> bool:
    """The PP engine runs shard_map manual over ``pp`` only (ZeRO/TP/DP
    stay automatic) and reads ``lax.axis_index`` inside — legacy (0.4.x)
    partial-auto shard_map lowers that to a bare PartitionId, which XLA's
    SPMD partitioner rejects ("PartitionId instruction is not supported
    for SPMD partitioning").  Probe the exact shape once; genuinely
    environment-specific (current jax lowers it fine), same root cause as
    the ``__graft_entry__`` self-test failure."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.utils import compat

    devs = jax.devices()
    if len(devs) < 8:
        return True
    mesh = Mesh(np.asarray(devs[:8]).reshape(2, 4), ("pp", "dp"))
    try:
        jax.jit(compat.shard_map(
            lambda a: a + jax.lax.axis_index("pp"), mesh=mesh,
            in_specs=P(), out_specs=P(), check_vma=False,
            axis_names={"pp"})).lower(jnp.zeros((2,), jnp.int32)).compile()
        return True
    except Exception as e:
        # ONLY the known lowering gap may skip; anything else (a compat
        # shim regression, a real in-repo bug) must fail loudly
        if "PartitionId" in repr(e):
            return False
        raise


if not _partial_manual_axis_index_lowers():
    pytest.skip(
        "legacy partial-auto shard_map cannot lower axis_index "
        "(XLA 'PartitionId instruction is not supported' — pre-existing, "
        "environment-specific; passes on current jax)",
        allow_module_level=True)


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def _make(mesh_cfg, gas=4):
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", scan_layers=True))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "mesh": mesh_cfg,
    })
    engine.init_params()
    return engine


def test_pp_engine_trains():
    e_pp = _make({"pp": 2, "dp": 4})
    batch = token_batch(e_pp.train_batch_size, 32, 512, seed=0)
    losses = [float(e_pp.train_batch(batch)) for _ in range(6)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]  # memorizes the repeated batch


def test_pp_loss_matches_non_pp_exactly():
    """Same dp_world on both sides → identical batches → identical losses.
    SGD so tiny bf16 grad noise can't sign-flip the update (Adam would)."""
    gas = 4
    opt = {"type": "sgd", "params": {"lr": 0.05}}
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", scan_layers=True))
    e_pp, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1, "gradient_accumulation_steps": gas,
        "optimizer": opt, "mesh": {"pp": 2, "dp": 4}})
    e_pp.init_params()
    batch = token_batch(e_pp.train_batch_size, 32, 512, seed=1)
    l_pp = [float(e_pp.train_batch(batch)) for _ in range(2)]

    mesh_mod.set_mesh(None)
    from deepspeed_tpu.comm.mesh import build_mesh

    mesh4 = build_mesh({"dp": 4}, devices=jax.devices()[:4])  # no pp
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", scan_layers=True))
    e_ref, _, _, _ = deepspeed_tpu.initialize(model=model, mesh=mesh4, config={
        "train_micro_batch_size_per_gpu": 1, "gradient_accumulation_steps": gas,
        "optimizer": opt})
    e_ref.init_params()
    assert e_ref.train_batch_size == e_pp.train_batch_size
    l_ref = [float(e_ref.train_batch(batch)) for _ in range(2)]
    np.testing.assert_allclose(l_pp, l_ref, rtol=2e-3)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(e_pp.params)),
                    jax.tree_util.tree_leaves(jax.device_get(e_ref.params))):
        # bf16 compute in a different (pipelined) layout rounds differently;
        # loss parity above is the tight check
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=1e-4)


def test_pp_with_zero3():
    e = _make({"pp": 2, "fsdp": 4})
    # stage-3 fsdp sharding composes with pp-sharded layer stacks
    batch = token_batch(e.train_batch_size, 32, 512, seed=2)
    losses = [float(e.train_batch(batch)) for _ in range(3)]
    assert np.isfinite(losses).all()


def test_pp_uneven_layers_trains_and_matches_non_pp():
    """Heterogeneous partitioning (reference pipe/module.py:363
    ``partition_layers``): n_layer NOT divisible by stages.  The stack is
    zero-padded to ceil inside the step (a zero-weight pre-LN block is an
    exact identity), so the pipelined loss must match the non-PP engine
    bit-for-tolerance, and pad slots never drift (state stays canonical
    3-layer)."""
    gas = 4
    opt = {"type": "sgd", "params": {"lr": 0.05}}
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", n_layer=3,
                                        scan_layers=True))
    e_pp, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": opt, "mesh": {"pp": 2, "dp": 4}})
    e_pp.init_params()
    # canonical state: 3 layers, no pad slot stored
    h_leaf = jax.tree_util.tree_leaves(e_pp.params["h"])[0]
    assert h_leaf.shape[0] == 3
    batch = token_batch(e_pp.train_batch_size, 32, 512, seed=11)
    l_pp = [float(e_pp.train_batch(batch)) for _ in range(3)]

    mesh_mod.set_mesh(None)
    from deepspeed_tpu.comm.mesh import build_mesh

    mesh4 = build_mesh({"dp": 4}, devices=jax.devices()[:4])
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", n_layer=3,
                                        scan_layers=True))
    e_ref, _, _, _ = deepspeed_tpu.initialize(model=model, mesh=mesh4, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas, "optimizer": opt})
    e_ref.init_params()
    l_ref = [float(e_ref.train_batch(batch)) for _ in range(3)]
    np.testing.assert_allclose(l_pp, l_ref, rtol=2e-3)


def test_pp_uneven_layers_1f1b():
    """The explicit-vjp schedules handle the padded stack too."""
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", n_layer=3,
                                        scan_layers=True))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 4,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "pipeline": {"schedule": "1f1b"},
        "mesh": {"pp": 2, "dp": 4},
    })
    engine.init_params()
    batch = token_batch(engine.train_batch_size, 32, 512, seed=12)
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_pp_embed_and_head_cond_gated():
    """The pipeline loops run the embed/head under ``lax.cond`` (one
    embed per microbatch on stage 0, one E×V head per consuming tick on
    the last stage) instead of compute-everywhere-and-mask; the compiled
    step must carry real HLO conditionals."""
    e = _make({"pp": 2, "dp": 4})
    batch = token_batch(e.train_batch_size, 32, 512, seed=13)
    hlo = e._compiled_train_step.lower(e.state, batch).compile().as_text()
    assert "conditional" in hlo


# ---------------- executed 1F1B (reference schedule.py:182) ----------------

def _make_sched(schedule, gas=4, lr=0.05):
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", scan_layers=True))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "sgd", "params": {"lr": lr}},
        "pipeline": {"schedule": schedule},
        "mesh": {"pp": 2, "dp": 4},
    })
    engine.init_params()
    return engine


def test_1f1b_matches_gpipe_exactly():
    """The explicit-vjp 1F1B loop computes the same loss and the same
    update as GPipe-via-autodiff (same math, different schedule)."""
    e_g = _make_sched("gpipe")
    batch = token_batch(e_g.train_batch_size, 32, 512, seed=3)
    l_g = [float(e_g.train_batch(batch)) for _ in range(3)]

    mesh_mod.set_mesh(None)
    e_1 = _make_sched("1f1b")
    l_1 = [float(e_1.train_batch(batch)) for _ in range(3)]
    np.testing.assert_allclose(l_1, l_g, rtol=2e-5, atol=1e-6)


def test_1f1b_memory_independent_of_microbatches():
    """Peak temp memory of the compiled 1F1B step must NOT scale with M
    (the GPipe autodiff residuals do) — the point of the schedule
    (reference TrainSchedule bounds live buffers at ~stages)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.parallel.pipeline import (onef1b_spmd_grads,
                                                 pipeline_spmd_loss)

    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", n_layer=4,
                                        scan_layers=True))
    mesh = mesh_mod.build_mesh({"pp": 4})
    mesh_mod.set_mesh(mesh)
    embed_fn, stage_fn, loss_fn, split_params, _ = model.pipeline_fns(4)
    params = seeded_params(model)
    shared, stage = split_params(params)

    def temp_bytes(fn, M):
        mbs = {"input_ids": np.zeros((M, 1, 32), np.int32),
               "labels": np.zeros((M, 1, 32), np.int32)}
        compiled = jax.jit(fn).lower(shared, stage, mbs).compile()
        ma = compiled.memory_analysis()
        return int(getattr(ma, "temp_size_in_bytes",
                           getattr(ma, "temp_size_bytes", 0)))

    def loss_1f1b(shared, stage, mbs):
        return onef1b_spmd_grads(
            mesh, shared, stage, mbs, jnp.float32(1.0),
            embed_fn=embed_fn, stage_fn=stage_fn, loss_fn=loss_fn,
            stage_params_layer_dim_spec=P("pp"))

    def loss_gpipe(shared, stage, mbs):
        def f(s, st):
            return pipeline_spmd_loss(
                mesh, s, st, mbs, embed_fn=embed_fn, stage_fn=stage_fn,
                loss_fn=loss_fn, stage_params_layer_dim_spec=P("pp"))
        return jax.value_and_grad(f, argnums=(0, 1))(shared, stage)

    b8, b32 = temp_bytes(loss_1f1b, 8), temp_bytes(loss_1f1b, 32)
    g8, g32 = temp_bytes(loss_gpipe, 8), temp_bytes(loss_gpipe, 32)
    if 0 in (b8, b32, g8, g32):
        pytest.skip("backend reports no temp memory analysis")
    # 4x microbatches: 1F1B temp stays ~flat, GPipe grows with M
    assert b32 < 1.6 * b8, (b8, b32)
    assert g32 > 2.0 * g8, (g8, g32)
    assert b32 < g32


def test_interleaved_matches_gpipe_exactly():
    """Executed interleaved 1F1B (V=2 virtual stages): same losses as
    GPipe — activations traverse the ring V times through the same
    per-chunk math."""
    gas = 4
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", n_layer=4,
                                        scan_layers=True))
    e_g, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "sgd", "params": {"lr": 0.05}},
        "mesh": {"pp": 2, "dp": 4},
    })
    e_g.init_params()
    batch = token_batch(e_g.train_batch_size, 32, 512, seed=5)
    l_g = [float(e_g.train_batch(batch)) for _ in range(3)]

    mesh_mod.set_mesh(None)
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", n_layer=4,
                                        scan_layers=True))
    e_i, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "sgd", "params": {"lr": 0.05}},
        "pipeline": {"schedule": "interleaved", "virtual_stages": 2},
        "mesh": {"pp": 2, "dp": 4},
    })
    e_i.init_params()
    l_i = [float(e_i.train_batch(batch)) for _ in range(3)]
    np.testing.assert_allclose(l_i, l_g, rtol=2e-5, atol=1e-6)


def test_interleaved_params_pre_permuted_no_step_alltoall(tmp_path):
    """Round-2 verdict item 3: the interleaved step must not regather the
    pp-sharded layer stack per step.  The stack is stored in local-slot
    order (permuted once at init), so the compiled step HLO carries no
    all-to-all; checkpoints stay canonical (a gpipe engine resumes them)."""
    gas = 4
    cfg_i = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "pipeline": {"schedule": "interleaved", "virtual_stages": 2},
        "mesh": {"pp": 2, "dp": 4},
    }
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", n_layer=4,
                                        scan_layers=True))
    e_i, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg_i)
    e_i.init_params()
    batch = token_batch(e_i.train_batch_size, 32, 512, seed=7)
    l_i = [float(e_i.train_batch(batch)) for _ in range(3)]

    hlo = e_i._compiled_train_step.lower(
        e_i.state, batch).compile().as_text()
    assert "all-to-all" not in hlo, \
        "interleaved step regathers the layer stack per step"

    # user-facing params view is canonical: matches a fresh global-order
    # init of the same seed/model
    e_i.save_checkpoint(str(tmp_path), tag="il")
    mesh_mod.set_mesh(None)
    model2 = GPT2LMHeadModel(gpt2_config("gpt2-tiny", n_layer=4,
                                         scan_layers=True))
    e_g, _, _, _ = deepspeed_tpu.initialize(model=model2, config={
        **cfg_i, "pipeline": {"schedule": "gpipe"}})
    e_g.init_params()
    e_g.load_checkpoint(str(tmp_path), tag="il")
    l_g = [float(e_g.train_batch(batch)) for _ in range(2)]
    l_i2 = [float(e_i.train_batch(batch)) for _ in range(2)]
    np.testing.assert_allclose(l_i2, l_g, rtol=2e-5, atol=1e-6)
