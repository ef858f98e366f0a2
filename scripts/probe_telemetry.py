"""Telemetry smoke probe: tiny train + serve loop, then assert the
telemetry layer produced (a) a non-empty metrics snapshot that renders
to Prometheus text and (b) a parseable Chrome-trace file with the
expected span names.

Runs on CPU with the same virtual 8-device mesh as the tier-1 tests:

    JAX_PLATFORMS=cpu python scripts/probe_telemetry.py [out_dir]

Writes ``trace.json`` + ``metrics.json`` + ``metrics.prom`` under
``out_dir`` (default: a temp dir) and prints a summary.  Exits nonzero
on any assertion failure — suitable as a CI smoke gate.
"""
import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

import numpy as np            # noqa: E402
import jax                    # noqa: E402
import jax.numpy as jnp       # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import deepspeed_tpu          # noqa: E402
from deepspeed_tpu.comm import mesh as mesh_mod            # noqa: E402
from deepspeed_tpu.telemetry import get_registry, recompile, trace  # noqa: E402

import flax.linen as nn       # noqa: E402


class _TinyModel(nn.Module):
    """Self-contained MSE model (mirrors tests/unit/simple_model.py)."""

    hidden: int = 16

    @nn.compact
    def __call__(self, x, y, deterministic: bool = True):
        h = nn.relu(nn.Dense(self.hidden)(x))
        out = nn.Dense(y.shape[-1])(h)
        return {"loss": jnp.mean((out - y) ** 2), "logits": out}

    def dummy_inputs(self, batch_size=2, seq_len=None):
        return {"x": jnp.zeros((batch_size, self.hidden)),
                "y": jnp.zeros((batch_size, self.hidden))}


def main(out_dir=None):
    out_dir = out_dir or tempfile.mkdtemp(prefix="dstpu_telemetry_")
    os.makedirs(out_dir, exist_ok=True)
    trace.enable()
    rng = np.random.default_rng(0)

    # ---- train: 3 steps --------------------------------------------
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=_TinyModel(),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
    engine.init_params()
    B = engine.train_batch_size
    for _ in range(3):
        x = rng.normal(size=(B, 16)).astype(np.float32)
        engine.train_batch({"x": x, "y": 0.1 * x})

    # ---- serve: 3 requests through the continuous batcher ----------
    mesh_mod.set_mesh(None)
    from deepspeed_tpu.inference.serving import ContinuousBatcher
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    cfg = gpt2_config("gpt2-tiny", dtype=jnp.float32)
    model = GPT2LMHeadModel(cfg)
    params = jax.tree_util.tree_map(
        lambda x: getattr(x, "value", x),
        model.init(jax.random.PRNGKey(0),
                   jnp.zeros((1, 8), jnp.int32))["params"],
        is_leaf=lambda x: hasattr(x, "names") and hasattr(x, "value"))
    eng = deepspeed_tpu.init_inference(model=model, mp_size=1,
                                       dtype=jnp.float32, params=params)
    batcher = ContinuousBatcher(eng, n_slots=2)
    prompts = [rng.integers(0, 512, size=(5,)).astype(np.int32)
               for _ in range(3)]
    outs = batcher.run(prompts, ticks=4, max_new_tokens=4)
    assert all(len(o) == 9 for o in outs), "serving emitted wrong lengths"
    batcher.latency_stats()

    # ---- assertions -------------------------------------------------
    trace_path = os.path.join(out_dir, "trace.json")
    trace.disable()
    trace.save(trace_path)
    with open(trace_path) as fh:
        data = json.load(fh)                       # parseable trace file
    names = sorted({e["name"] for e in data["traceEvents"]})
    assert len(names) >= 3, f"too few span names: {names}"
    for want in ("train/dispatch", "serve/prefill", "serve/decode-tick"):
        assert want in names, f"missing span {want!r} in {names}"

    reg = get_registry()
    snap = reg.snapshot()
    assert snap, "metrics snapshot is empty"
    assert snap["train_steps_total"]["samples"][0]["value"] >= 3
    assert snap["serving_requests_completed_total"]["samples"][0]["value"] >= 3
    hot_recompiles = [s for s in snap["xla_recompiles_total"]["samples"]
                      if s["value"] > 0]
    assert not hot_recompiles, f"hot loops recompiled: {hot_recompiles}"
    with open(os.path.join(out_dir, "metrics.json"), "w") as fh:
        json.dump(snap, fh, indent=1)
    prom = reg.render_prometheus()
    assert "train_steps_total" in prom and "serving_ttft_seconds" in prom
    with open(os.path.join(out_dir, "metrics.prom"), "w") as fh:
        fh.write(prom)

    print(f"telemetry probe OK: {len(data['traceEvents'])} trace events "
          f"({len(names)} span names), {len(snap)} metric families, "
          f"0 hot-loop recompiles -> {out_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else None))
