"""Autotuner: compile-only probing picks a valid config (reference
``tests/unit/test_autotuning.py`` analog)."""
import numpy as np
import pytest

import jax

from deepspeed_tpu.autotuning import Autotuner
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

# the autotuner scores candidates against the chip's physics; on the CPU
# mesh these tests exercise its search and bookkeeping against a nominal row
pytestmark = pytest.mark.usefixtures("nominal_cpu_physics")


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def test_autotuner_probes_and_picks():
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny"))
    tuner = Autotuner(
        model,
        base_config={"optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                     "steps_per_print": 10**9},
        micro_batches=[1, 2],
        zero_stages=[0, 2],
        remat_options=[False],
        # the kernel knobs (x3 here) have their own test, the next but one
        kernel_options=[{}],
        seq_len=32)
    best = tuner.tune()
    assert "train_micro_batch_size_per_gpu" in best
    assert best["zero_optimization"]["stage"] in (0, 2)
    probes = [r for r in tuner.results if not r.error]
    assert probes, [r.error for r in tuner.results]
    assert all(r.flops > 0 for r in probes)
    # bigger micro-batch → more flops per step
    by_micro = {r.config_overrides["train_micro_batch_size_per_gpu"]: r.flops
                for r in probes
                if r.config_overrides["zero_optimization.stage"] == 0}
    if len(by_micro) == 2:
        assert by_micro[2] > by_micro[1]


def test_autotuner_trial_engine_isolated():
    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny"))
    tuner = Autotuner(model, base_config={
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}}},
        micro_batches=[1], zero_stages=[3], remat_options=[True], seq_len=32)
    r = tuner._probe(3, 1, True)
    assert r.error is None, r.error
    assert np.isfinite(r.est_step_time)


def test_autotuner_kernel_options_space():
    """The search space includes model kernel knobs (fused_mlp) and the
    winning kernel override lands in the returned config."""
    import jax.numpy as jnp

    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    mesh_mod.set_mesh(None)
    try:
        model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", dtype=jnp.float32))
        tuner = Autotuner(model, {"train_micro_batch_size_per_gpu": 1},
                          micro_batches=[1], zero_stages=[1],
                          remat_options=[False])
        assert {} in tuner.kernel_options
        assert {"fused_mlp": True} in tuner.kernel_options
        assert {"scan_layers": False} in tuner.kernel_options
        cfg = tuner.tune()
        kernels_probed = {tuple(sorted(r.config_overrides["kernel"].items()))
                          for r in tuner.results}
        assert len(kernels_probed) == 3
        assert "autotuned" in cfg
    finally:
        mesh_mod.set_mesh(None)


def test_autotuner_flash_knobs_probed_and_carried():
    """Explicit flash tiling kernel_options probe cleanly and the winner's
    override lands in model_overrides (on CPU the flash kernel itself
    can't engage, but the config plumbing is backend-independent)."""
    import jax.numpy as jnp

    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    mesh_mod.set_mesh(None)
    try:
        model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", dtype=jnp.float32))
        tuner = Autotuner(model, {"train_micro_batch_size_per_gpu": 1},
                          micro_batches=[1], zero_stages=[1],
                          remat_options=[False],
                          kernel_options=[{"flash_block": (256, 256)},
                                          {"flash_block": (128, 256)}])
        cfg = tuner.tune()
        assert all(r.error is None for r in tuner.results), \
            [r.error for r in tuner.results]
        # model_overrides carry the winning kernel knob AND the remat
        # flag (tune() pins remat both directions since round 3)
        mo_kernel = {k: v for k, v in cfg["model_overrides"].items()
                     if k != "remat"}
        assert mo_kernel in (
            {"flash_block": (256, 256)}, {"flash_block": (128, 256)})
        assert cfg["model_overrides"]["remat"] is False
        # the override reconfigures the model when fed back to initialize()
        import deepspeed_tpu

        mesh_mod.set_mesh(None)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model,
            config={"train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "model_overrides": dict(cfg["model_overrides"])})
        mo = cfg["model_overrides"]
        for k, v in mo.items():
            got = getattr(engine.model.cfg, k)
            assert got == v or got == tuple(v)
    finally:
        mesh_mod.set_mesh(None)


def test_model_overrides_applied_by_engine():
    """An autotuned config with model_overrides reconfigures the model."""
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    mesh_mod.set_mesh(None)
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2LMHeadModel(gpt2_config("gpt2-tiny", dtype=jnp.float32)),
            config={"train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "model_overrides": {"fused_mlp": True},
                    "autotuned": {"note": "from a prior tune()"}})
        assert engine.model.cfg.fused_mlp is True
    finally:
        mesh_mod.set_mesh(None)


def test_northstar_space_probes_and_picks():
    """Round-2 verdict item 8: the billion-param single-chip recipe
    (ZeRO-3, micro, remat policy, loss_chunk, adamw8bit, scan_layers) is
    a machine-searchable space, not BENCH_NORTHSTAR prose.  At tiny
    scale everything fits; the point is that all dimensions probe
    cleanly and the winner round-trips through initialize()."""
    import deepspeed_tpu

    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", scan_layers=False,
                                        n_layer=2))
    tuner = Autotuner.northstar_space(
        model,
        base_config={"optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                     "steps_per_print": 10**9},
        micro_batches=[2],      # the first test probes the micro-batches
        remat_options=[False],
        kernel_options=[{"scan_layers": False, "loss_chunk": None},
                        {"scan_layers": False, "loss_chunk": 64}],
        seq_len=32)
    best = tuner.tune()
    probes = [r for r in tuner.results if not r.error]
    assert probes, [r.error for r in tuner.results]
    # both optimizer variants probed
    opts = {r.config_overrides["optimizer"].get("type")
            for r in tuner.results}
    assert opts == {"adamw8bit", "adamw"}
    assert best["zero_optimization"]["stage"] == 3
    assert best["optimizer"]["type"] in ("adamw8bit", "adamw")
    # winner config drives a real engine (autotuned recipe is runnable)
    mesh_mod.set_mesh(None)
    best.pop("autotuned")
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=best)
    engine.init_params()
    batch = engine.model.dummy_inputs(batch_size=engine.train_batch_size,
                                      seq_len=32)
    loss = engine.train_batch(batch)
    assert np.isfinite(float(jax.device_get(loss)))
