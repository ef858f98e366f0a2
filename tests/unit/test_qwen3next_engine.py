"""Qwen3-Next through the normal path (PR 48): ``deepspeed_tpu.initialize``
-> ``engine.train_batch(data_iter)`` under ZeRO-3 and ``adamw8bit`` on the
CPU mesh, the new leaves sharded, no side script.
"""
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.models.llama import LlamaForCausalLM
from tests.unit.test_qwen3next import S, VOCAB, _config


def test_the_engine_trains_it_under_zero3_with_the_new_leaves_sharded():
    import deepspeed_tpu

    mesh_lib.set_mesh(None)
    cfg = _config(4, 4, dtype=jnp.bfloat16, loss_chunk=16, remat=True,
                  remat_policy="dots_saveable+flash", attn_impl="auto")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg), config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw8bit",
                          "params": {"lr": 1e-2, "weight_decay": 0.1}},
            "zero_optimization": {"stage": 3}, "gradient_clipping": 1.0,
            "mesh": {"fsdp": -1}, "steps_per_print": 10**9})
    engine.init_params()
    n = engine.dp_world
    assert n == jax.device_count() > 1
    lin = engine.state.params["layers_0"]["linear_attn"]
    for leaf, shape in (("in_proj_qkvz_kernel", (32, 96)),
                        ("in_proj_ba_kernel", (32, 8)),
                        ("conv_kernel", (64, 4)),
                        ("out_proj_kernel", (32, 32))):
        assert lin[leaf].shape == shape
        shard = lin[leaf].addressable_shards[0].data.shape
        assert int(np.prod(shard)) * n == int(np.prod(shape)), (leaf, shard)

    def batches():          # the same rows every step: something to learn
        ids = np.random.default_rng(0).integers(
            0, VOCAB, (engine.train_batch_size, S)).astype(np.int32)
        while True:
            yield {"input_ids": ids, "labels": ids}

    data = batches()
    before = {k: np.asarray(lin[k]) for k in ("conv_kernel", "A_log")}
    losses = [float(engine.train_batch(data_iter=data)) for _ in range(6)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    after = engine.state.params["layers_0"]["linear_attn"]
    for k, b in before.items():
        assert np.abs(np.asarray(after[k]) - b).max() > 0, k
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    assert any(s == "gated_delta" and i == "xla" and n
               for s, i, _, n in dispatch_report())


