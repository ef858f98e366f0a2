"""The benchmark's second cell, ``train-olmoe-z3-1chip`` (PR 26): its
names resolve to files, its rehearsal passes on the CPU, its operation
counts are what a hand computes, and its readers give nothing (and do not
raise) on a program that lacks what they read.  Host-only, nothing timed.
"""
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops_moe
from benchmark.harness import manifest as M
from benchmark.layer_metrics import (expert_gemm_roofline,
                                     expert_gemm_share_pct,
                                     moe_load_imbalance)

ROOT = M.ROOT
CELL = "train-olmoe-z3-1chip"
CONFIG = "olmoe-1b-7b-z3-8bit"
# what a cut may name: depth and context, never a width
CUTS = {"n_positions", "num_hidden_layers", "n_layer",
        "max_position_embeddings"}


@pytest.fixture(scope="module")
def manifest():
    return M.load_manifest(ROOT)


@pytest.fixture(scope="module")
def cell(manifest):
    return M.load_cell(manifest, CELL, ROOT)


@pytest.fixture(scope="module")
def with_pending(manifest):
    m = copy.deepcopy(manifest)
    folder = os.path.join(ROOT, "benchmark", "pending")
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name)) as f:
            p = json.load(f)
        m["configs"].append(p["config"])
        m["workloads"].append(p["workload"])
        m["end_to_end"].insert(0, p["end_to_end"])
        m["per_layer"].extend(p["per_layer"])
    M.check_manifest(m)
    return m


@pytest.mark.parametrize("which", ["manifest", "with_pending"])
def test_every_cell_config_mix_reader_driver_and_reference_loads(which, request):
    """``test_benchmark.py``'s test of the same name, whole, with its last
    assertion widened from ``{n_positions}`` to :data:`CUTS` (conftest.py
    says why the original is expected to fail)."""
    manifest = request.getfixturevalue(which)
    used = set()
    for w in manifest["workloads"]:
        c = M.load_cell(manifest, w["name"], ROOT)
        used.add(c.config_name)
        assert c.config["reduced"] == next(
            x["reduced"] for x in manifest["configs"]
            if x["name"] == c.config_name)
        assert [m["name"] for m in c.end_to_end].count("setup_s") == 1
        assert len(c.end_to_end) >= 2 and len(c.per_layer) >= 1
        assert callable(c.driver().run)
        assert callable(c.reference().logits)
    assert used == {c["name"] for c in manifest["configs"]}
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        assert set(c["reduced"]) <= CUTS


def test_the_manifest_gained_one_config_one_cell_three_metrics(manifest, cell):
    assert [c["name"] for c in manifest["configs"]][-1] == CONFIG
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL
    entry = manifest["workloads"][-1]
    assert entry["chips"] == 1 and entry["traffic"] == "packed-4k"
    assert manifest["configs"][-1]["reduced"] == ["num_hidden_layers"]
    assert [m["name"] for m in manifest["per_layer"]][-3:] == [
        "expert_gemm_share_pct", "expert_gemm_roofline", "moe_load_imbalance"]
    for m in manifest["per_layer"][-3:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tokens_per_s_chip"
    names = [m["name"] for m in cell.per_layer]
    assert names[:11] == [
        "train_step_ms", "train_mfu_pct", "flash_share_pct", "flash_roofline",
        "device_idle_pct.train", "train_host_ms", "train_input_ms",
        "train_dispatch_ms", "setup_trace_lower_s",
        "setup_backend_compile_s", "setup_init_params_s"]
    assert [m["name"] for m in cell.end_to_end] == [
        "train_tokens_per_s_chip", "setup_s"]
    # the cells that were there kept their places at the head of each list
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL


def test_the_configuration_file_is_the_catalog_row_cut_in_depth_alone(cell):
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    differs = {k for k, v in published.items() if cell.config[k] != v}
    assert differs == {"num_hidden_layers"} == set(cell.config["reduced"])
    assert cell.config["num_hidden_layers"] == 3
    assert cell.traffic["seq_len"] == 4096
    assert cell.traffic["eos_token_id"] == 50279
    assert cell.config["micro_per_device"] * cell.traffic["seq_len"] == 8192
    tol = cell.config["reference_check"]
    assert 0 < tol["loss_abs_tol"] <= 0.02 and len(tol["reason"]) > 40


def test_the_driver_builds_the_model_from_the_file_as_data(cell):
    sys.path.insert(0, ROOT)
    driver = cell.driver()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    model, cfg = driver.model_config(conf)
    assert type(cfg).__name__ == "LlamaConfig"
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size) == (2048, 3, 128, 1024, 50304)
    assert cfg.qk_norm and cfg.loss_chunk == 8192 and not cfg.scan_layers
    # the source's initializer for every matrix; the embedding table's
    # departure is the benchmark's (init_scale), not a field of the program
    assert cfg.initializer_range == 0.02 and conf["init_scale"] == {
        "embed_tokens": 50.0}
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.drop_tokens,
            cfg.moe.norm_topk_prob, cfg.moe.expert_act) == (
        64, 8, False, False, "swiglu")
    assert (cfg.moe.aux_loss_weight, cfg.moe.z_loss_weight) == (0.01, 0.001)
    kw = driver.reference_kwargs(conf)
    assert kw == {"n_layer": 3, "n_head": 16, "vocab_size": 50304, "top_k": 8,
                  "norm_topk_prob": False, "eps": 1e-05, "theta": 10000,
                  "aux_loss_weight": 0.01, "z_loss_weight": 0.001}
    # the program's own count of active parameters agrees with the benchmark's
    assert model.flops_per_token() == pytest.approx(
        6.0 * (flops_moe.active_matmul_params(conf) + 50304 * 2048)
        + 12 * 3 * 2048 * 4096)


def test_flops_moe_against_hand_computed_numbers(cell):
    conf = cell.config
    block = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert block == 67_239_936
    assert flops_moe.active_matmul_params(conf) == 304_742_400 \
        == 3 * block + 103_022_592
    assert flops_moe.active_matmul_params(
        dict(conf, num_hidden_layers=16)) == 1_178_861_568
    attn = 3 * 2.0 * 3 * 2048 * 4096
    assert flops_moe.causal_attention_flops_per_token(conf, 4096, 3) == attn
    assert flops_moe.train_flops_per_token(conf, 4096) == \
        6.0 * 304_742_400 + attn == pytest.approx(1.979e9, rel=1e-3)
    assert flops_moe.flash_train_bytes_per_token(conf) == 12 * 3 * 2048 * 2
    # grouped query attention moves fewer K/V bytes, the same FLOPs
    gqa = dict(conf, num_key_value_heads=4)
    assert flops_moe.flash_train_bytes_per_token(gqa) == 6 * 3 * (16 + 4) * 128 * 2
    assert flops_moe.active_matmul_params(gqa) == \
        304_742_400 - 3 * 2 * 2048 * (16 - 4) * 128


def test_grouped_matmul_flops_and_bytes_from_shapes(cell):
    conf, T = cell.config, 8192
    rows = T * 8
    assert flops_moe.expert_gemm_flops_per_step(conf, T) == \
        3 * 3 * 3 * 2.0 * rows * 2048 * 1024
    one = 64 * 2048 * 1024 + rows * (2048 + 1024)
    assert flops_moe.expert_gemm_bytes_per_step(conf, T) == 3 * 9.0 * one * 2
    # compute-bound on the v5e: 37.7 ms of MXU against 22.1 ms of HBM a step
    peak = M.load_peaks(ROOT)["TPU v5 lite"]
    t, bound = flops_moe.roofline_seconds(
        flops_moe.expert_gemm_flops_per_step(conf, T),
        flops_moe.expert_gemm_bytes_per_step(conf, T), peak)
    assert bound == "compute" and t == pytest.approx(0.03767, rel=1e-3)


class _Trace:
    busy_s, window_s = 4.0, 5.0

    def __init__(self, seconds):
        self.seconds = seconds

    def ops_matching(self, pattern):
        assert pattern == "^t?gmm$"
        return self.seconds


def test_expert_gemm_readers_on_hand_made_observations(cell):
    peak = M.load_peaks(ROOT)["TPU v5 lite"]
    obs = {"cell": cell, "peak": peak, "trace": _Trace(2.0), "steps": 20,
           "window_s": 5.0,
           "expert_gemm_flops_per_step":
               flops_moe.expert_gemm_flops_per_step(cell.config, 8192),
           "expert_gemm_bytes_per_step":
               flops_moe.expert_gemm_bytes_per_step(cell.config, 8192)}
    assert expert_gemm_share_pct.read(obs) == 50.0
    assert expert_gemm_roofline.read(obs) == pytest.approx(
        100 * 20 * 0.03767 / 2.0, rel=1e-3)
    # nothing to read: no trace, no such kernel in it, no counts, no names
    for hole in ({"trace": None}, {"trace": _Trace(0.0)}):
        assert expert_gemm_share_pct.read(dict(obs, **hole)) is None
        assert expert_gemm_roofline.read(dict(obs, **hole)) is None
    bare = {k: v for k, v in obs.items() if not k.startswith("expert_gemm")}
    assert expert_gemm_roofline.read(bare) is None
    other = M.load_cell(M.load_manifest(ROOT), "train-xl-z3-1chip", ROOT)
    assert expert_gemm_share_pct.read(dict(obs, cell=other)) is None
    assert expert_gemm_roofline.read(dict(obs, cell=other)) is None


def test_load_imbalance_is_the_median_of_max_over_mean():
    a = np.zeros((2, 4))
    b = a + np.array([[10, 10, 10, 10], [40, 0, 0, 0]])      # 1.0 and 4.0
    c = b + np.array([[20, 0, 10, 10], [10, 10, 10, 10]])    # 2.0 and 1.0
    obs = {moe_load_imbalance.COUNTER: [a, b, b, c, None]}   # b,b: no step
    assert moe_load_imbalance.read(obs) == pytest.approx(1.5)
    assert moe_load_imbalance.read({}) is None
    assert moe_load_imbalance.read({moe_load_imbalance.COUNTER: [None, a]}) is None


def test_load_imbalance_reads_the_programs_counter():
    from deepspeed_tpu.parallel.moe import record_stats
    from deepspeed_tpu.telemetry import get_registry

    get_registry().clear()
    assert moe_load_imbalance.snapshot() is None      # no counter: nothing
    stats = {"tokens_per_expert": np.array([[3, 1], [0, 4]]),
             "dropped": np.zeros(2), "balance_loss": np.ones(2),
             "router_z": np.ones(2)}
    record_stats(stats)
    record_stats(stats)
    np.testing.assert_array_equal(moe_load_imbalance.snapshot(),
                                  [[6, 2], [0, 8]])
    get_registry().clear()


class _Ctx:
    def __init__(self):
        self.notes, self.lines = [], []

    def log(self, msg):
        self.lines.append(msg)

    def check(self, ok, what):
        if not ok:
            self.notes.append(what)


@pytest.mark.parametrize("fault", [None, "lost_group", "shifted_boundaries"])
def test_expert_check_sees_what_the_loss_check_cannot(cell, fault, monkeypatch):
    """The driver's ``check_experts`` at the rehearsal's widths.  A sound
    program passes.  A grouped matmul that multiplies one expert's rows by
    nothing, or takes every group's size from its neighbour, is refused,
    while the whole model's loss moves by less than ``loss_abs_tol``: the
    experts are a few percent of the residual stream, which is why the
    loss check alone cannot be ``correct``."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from deepspeed_tpu.parallel import moe

    driver, reference = cell.driver(), cell.reference()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    conf.update(cell.config["rehearse"])
    tol = conf["reference_check"]
    model, cfg = driver.model_config(conf)
    ids = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, cfg.max_position_embeddings)), jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    params["embed_tokens"] = params["embed_tokens"] * \
        conf["init_scale"]["embed_tokens"]
    hidden = []
    reference.loss_parts(params, ids, ffn_inputs=hidden,
                         **driver.reference_kwargs(conf))
    assert len(hidden) == conf["num_hidden_layers"]

    def loss():
        return float(model.apply({"params": params}, ids, labels=ids)["loss"])

    sound_loss = loss()
    real = moe.grouped_matmul

    def faulty(lhs, rhs, sizes, **kw):
        if fault == "lost_group":
            rhs = rhs.at[2].set(0.0)
        elif fault == "shifted_boundaries":
            sizes = jnp.roll(sizes, 1)
        return real(lhs, rhs, sizes, **kw)

    monkeypatch.setattr(moe, "grouped_matmul", faulty)
    ctx = _Ctx()
    driver.check_experts(ctx, cfg, conf, reference, params, hidden)
    assert "expert check" in ctx.lines[0]
    if fault is None:
        assert ctx.notes == []
    else:
        assert len(ctx.notes) == 1 and "sparse FFN" in ctx.notes[0]
        assert abs(loss() - sound_loss) < tol["loss_abs_tol"]


def test_rehearsal_of_the_olmoe_cell_prints_a_correct_line():
    """The whole control flow on the CPU at tiny widths that keep the
    shape (16 experts, top-8, QK-norm, chunked untied head): build from the
    file, reference check, warm-up, window, routing counters, checks."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["workload"] == CELL
    assert line["correct"] is True and line["notes"] == []
    assert line["failed"] == 0 and line["compiles_in_window"] == 0
    assert line["attempted"] >= 3
    assert "reference check: engine loss" in p.stderr
