"""The benchmark's fourth cell, ``train-trinity-mini-8k-1chip`` (PR 33): its
names resolve to files, its configuration is the catalog row cut as the
guide allows, its operation counts are what a hand computes, its new reader
gives nothing (and does not raise) where there is nothing to read, its
comparison refuses each named fault, and its rehearsal passes on the CPU.
Host-only, nothing timed.

The three tests at the top replace the three of ``test_mellum2_cell.py``
that pin the manifest's lists to three cells (``tests/conftest.py``).  They
are written over **prefixes and subsets**: the cells an accepted PR added
are a prefix of every list they were appended to, in the order they were
accepted, and a cut is depth, dense depth, context, experts held or
vocabulary, never a width: the next configuration adds a line to
``ACCEPTED`` in a file of its own, or nothing, and needs no copy of these.
"""
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops_trinity as F
from benchmark.harness import manifest as M
from benchmark.layer_metrics import (moe_expert_bias_spread,
                                     moe_held_pair_pct, moe_load_imbalance)

ROOT = M.ROOT
CELL = "train-trinity-mini-8k-1chip"
CONFIG = "trinity-mini-z3-8bit"
# what a cut may name (model-configs guide, section 4): depth, leading
# dense depth, context, the experts held here, the vocabulary slice
CUTS = {"n_positions", "num_hidden_layers", "n_layer", "num_dense_layers",
        "max_position_embeddings", "num_experts", "vocab_size"}


def is_a_width(key: str) -> bool:
    """What ``reduced`` may never name: a hidden, intermediate, latent,
    state or projection size, a head size, an expansion factor, the
    experts a token runs."""
    return key.endswith(("_dim", "_rank", "_size", "_width")) \
        and key != "vocab_size" or key in (
        "n_embd", "n_inner", "sliding_window", "num_experts_per_tok",
        "num_attention_heads", "num_key_value_heads")


# every cell an accepted PR added, in the order it was accepted, with what
# it was accepted at; a later cell stands behind these in every list
ACCEPTED = {
    "train-xl-z3-1chip": ("gpt2-xl-z3-8bit", "packed-1k", 1, []),
    "train-olmoe-z3-1chip": ("olmoe-1b-7b-z3-8bit", "packed-4k", 1,
                             ["num_hidden_layers"]),
    "train-mellum2-8k-1chip": ("mellum2-12b-a2.5b-z3-8bit", "packed-8k", 1,
                               ["num_hidden_layers", "num_experts",
                                "vocab_size"]),
    CELL: (CONFIG, "packed-8k-25k", 1,
           ["num_hidden_layers", "num_dense_layers", "num_experts",
            "vocab_size"]),
}
# per-layer metrics by the first accepted cell that reports them
SINCE = {
    "train-xl-z3-1chip": [
        "train_step_ms", "train_mfu_pct", "flash_share_pct", "flash_roofline",
        "device_idle_pct.train", "train_host_ms", "train_input_ms",
        "train_dispatch_ms", "setup_trace_lower_s", "setup_backend_compile_s",
        "setup_init_params_s"],
    "train-olmoe-z3-1chip": ["expert_gemm_share_pct", "expert_gemm_roofline",
                             "moe_load_imbalance"],
    "train-mellum2-8k-1chip": ["flash_window_roofline", "flash_full_roofline",
                               "flash_window_share_pct", "moe_held_pair_pct"],
    CELL: ["moe_expert_bias_spread"],
}


def is_prefix(short, long) -> bool:
    return list(long[:len(short)]) == list(short)


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
def test_every_cell_loads_and_is_cut_only_as_the_guide_allows(which, request):
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
        assert set(c["reduced"]) <= CUTS, c["name"]
        assert not any(is_a_width(k) for k in c["reduced"]), c["name"]
    assert is_a_width("hidden_size") and is_a_width("moe_intermediate_size") \
        and is_a_width("head_dim") and is_a_width("kv_lora_rank") \
        and not any(is_a_width(k) for k in CUTS)


def test_every_accepted_cell_is_still_there_with_its_values(manifest):
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    for name, (config, traffic, chips, reduced) in ACCEPTED.items():
        w = cells[name]
        assert (w["config"], w["traffic"], w["chips"]) == (config, traffic,
                                                           chips)
        assert configs[config]["reduced"] == reduced
    # the order of what was there is the order it was accepted in, and
    # what came later stands behind it
    order = list(ACCEPTED)
    assert is_prefix(order, [w["name"] for w in manifest["workloads"]])
    assert is_prefix([v[0] for v in ACCEPTED.values()],
                     [c["name"] for c in manifest["configs"]])
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert is_prefix(order, e2e["train_tokens_per_s_chip"]["workloads"])
    assert e2e["train_tokens_per_s_chip"]["bound"] == 0.01
    assert e2e["setup_s"]["bound"] == 0.1 and manifest["run_seconds"] == 50
    # a metric lists the accepted cells from its first on, in order (the
    # kinds of attention are Mellum 2's and Trinity's alone, and so on)
    for first, names in SINCE.items():
        since = order[order.index(first):]
        for name in names:
            assert is_prefix(since, by_name[name]["workloads"]), name
            assert by_name[name]["moves"] in e2e
    assert is_prefix([n for names in SINCE.values() for n in names],
                     [m["name"] for m in manifest["per_layer"]])
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e


def test_no_metric_lost_a_cell_and_each_cell_kept_its_own(manifest):
    names = lambda c: [m["name"] for m in M.load_cell(  # noqa: E731
        manifest, c, ROOT).per_layer]
    so_far = []
    for cell_name, new in SINCE.items():
        so_far = so_far + new
        assert names(cell_name) == so_far, cell_name
    assert len(SINCE["train-xl-z3-1chip"]) == 11
    spread = next(m for m in manifest["per_layer"]
                  if m["name"] == "moe_expert_bias_spread")
    assert spread == {"name": "moe_expert_bias_spread", "unit": "score",
                      "better": "lower", "source": "program_counter",
                      "layer": "experts", "moves": "train_tokens_per_s_chip",
                      "workloads": [CELL]}


# ----------------------------------------------------------------------
# the configuration
# ----------------------------------------------------------------------
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
STARRED = ("embedding_multiplier", "attention_gate", "qk_norm", "rope",
           "norms", "expert_bias", "expert_weight", "load_balance_coeff")


def test_the_configuration_file_is_the_catalog_row_cut_four_ways(cell):
    conf = cell.config
    differs = {k for k, v in PUBLISHED.items() if conf[k] != v}
    assert differs == {"num_hidden_layers", "num_dense_layers", "num_experts",
                       "vocab_size"} == set(conf["reduced"])
    assert (conf["num_hidden_layers"], conf["num_dense_layers"],
            conf["num_experts"], conf["vocab_size"]) == (5, 1, 16, 25024)
    for key in conf["reduced"]:
        assert conf["published"][key] == PUBLISHED[key]
    assert conf["routed_experts"] == conf["moe"]["routed_experts"] == 128
    # the nested group whole: 32 layer types, of which the first five run:
    # a dense sliding layer, then sliding, sliding, full, sliding
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert conf["layer_types"] == period * 8
    assert F.layer_kinds(conf) == period + ["sliding_attention"]
    # floors: a whole period in four layers after the dense one, >= 8
    # experts, >= 1/8 of the vocabulary
    after = F.layer_kinds(conf)[conf["num_dense_layers"]:]
    assert len(after) == 4 and sorted(after) == sorted(period)
    assert conf["num_experts"] >= 8 and conf["vocab_size"] * 8 >= 200192
    assert "eight" in conf["stands_for"] and "16" in conf["stands_for"]
    # the moe section says the routing under the config's own names;
    # route_norm is the program's norm_topk_prob
    moe = conf["moe"]
    for key in ("score_func", "route_scale", "num_shared_experts"):
        assert moe[key] == conf[key] == PUBLISHED[key]
    assert conf["norm_topk_prob"] is conf["route_norm"] is True
    assert "norm_topk_prob" in conf["assumed"]["route_norm"]
    # the published rate stays under its key and the run's has a key of its
    # own: the smallest that settles in the warm-up a run affords, both
    # readings written out
    assert "load_balance_coeff" not in moe
    assert conf["load_balance_coeff"] == 0.001 <= moe["bias_update_rate"] \
        <= 0.02
    assert "0.001" in conf["assumed"]["balancing"] and \
        str(moe["bias_update_rate"]) in conf["assumed"]["balancing"]
    assert moe["aux_loss_weight"] == 0.0 and moe["first_expert"] == 16
    # what the config has no key for is written out, each with its reason
    for star in STARRED:
        assert "the config has no key" in conf["assumed"][star], star
    for key in ("eos_token_id", "document_mask", "initializer_range",
                "dropout", "balancing", "cut", "recipe", "rows"):
        assert len(conf["assumed"][key]) > 20, key
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["eos_token_id"] == 25023 < conf["vocab_size"]
    assert conf["micro_per_device"] * cell.traffic["seq_len"] in (24576, 32768)
    tol = conf["reference_check"]
    assert 0 < tol["loss_abs_tol"] <= 0.02
    for key in ("expert_rel_tol", "dense_rel_tol", "attention_rel_tol"):
        assert 0 < tol[key] < 0.1
    for why in ("reason", "expert_reason", "dense_reason",
                "attention_reason"):
        assert len(tol[why]) > 40


def test_the_driver_builds_the_model_from_the_file_as_data(cell):
    import jax

    sys.path.insert(0, ROOT)
    driver = cell.driver()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    model, cfg = driver.model_config(conf)
    assert type(cfg).__name__ == "LlamaConfig"
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_dense_layers,
            cfg.head_dim, cfg.kv_heads, cfg.intermediate_size,
            cfg.expert_size, cfg.vocab_size, cfg.sliding_window,
            cfg.rope_theta, cfg.rms_norm_eps) == (
        2048, 5, 1, 128, 4, 6144, 1024, 25024, 2048, 10000.0, 1e-5)
    assert cfg.kinds == ("sliding_attention",) * 3 + (
        "full_attention", "sliding_attention")
    assert cfg.mup_enabled and cfg.attn_gate and cfg.sandwich_norm
    assert cfg.qk_norm == "head" and cfg.rope_parameters is None
    assert cfg.rotates("sliding_attention")
    assert not cfg.rotates("full_attention")
    assert [cfg.sparse(i) for i in range(5)] == [False] + [True] * 4
    assert cfg.loss_chunk == 8192 and not cfg.scan_layers
    assert cfg.padded_vocab_size == 25088
    moe = cfg.moe
    assert (moe.num_experts, moe.routed, moe.first_expert, moe.top_k,
            moe.drop_tokens, moe.norm_topk_prob, moe.expert_act) == (
        16, 128, 16, 8, False, True, "swiglu")
    assert (moe.score_func, moe.route_scale, moe.num_shared_experts,
            moe.bias_update_rate, moe.aux_loss_weight) == (
        "sigmoid", 2.826, 1, conf["moe"]["bias_update_rate"], 0.0)
    kw = driver.reference_kwargs(conf)
    assert kw["first_expert"] == 16 and kw["routed_experts"] == 128
    assert kw["num_dense_layers"] == 1 and kw["route_scale"] == 2.826
    assert kw["layer_types"] == conf["layer_types"]
    # 705.7 M parameters held here (the issue reckoned 705 M)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 128), np.int32))["params"]
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    norms = 4 * 2048
    dense = attn + norms + 3 * 2048 * 6144
    sparse = attn + norms + 2048 * 128 + 128 + 17 * 3 * 2048 * 1024
    assert n == dense + 4 * sparse + 2 * 25088 * 2048 + 2048 == 705_736_448


def test_flops_against_hand_computed_numbers(cell):
    conf = cell.config
    E, H, KV, D, I, Fd = 2048, 32, 4, 128, 1024, 6144
    attn = 3 * E * H * D + 2 * E * KV * D
    assert attn == 25_165_824 + 2_097_152
    sparse = E * 128 + (1 + 8 * 16 / 128) * 3 * E * I
    assert sparse == 262_144 + 2 * 6_291_456
    active = 5 * attn + 3 * E * Fd + 4 * sparse + 25024 * E
    assert F.active_matmul_params(conf) == active
    assert active == pytest.approx(276.7e6, rel=1e-3)   # the issue's 277 M
    # twice the even share of pairs here: one more expert's worth a token
    assert F.active_matmul_params(conf, held=0.25) == \
        active + 4 * 8 * 0.125 * 3 * E * I
    banded = sum(min(i + 1, 2048) for i in range(8192)) / 8192
    full = sum(i + 1 for i in range(8192)) / 8192
    assert F.kept_keys_per_token(8192, 2048) == banded == 1792.125
    assert F.kept_keys_per_token(8192) == full == 4096.5
    one = lambda keys: 3 * 4.0 * H * D * keys   # noqa: E731
    assert F.attention_flops_per_token(conf, 8192, 3, F.SLIDING) == \
        4 * one(banded)
    assert F.attention_flops_per_token(conf, 8192, 3, F.FULL) == one(full)
    attention = 4 * one(banded) + one(full)
    assert F.causal_attention_flops_per_token(conf, 8192, 3) == attention
    assert attention / 3 == pytest.approx(184.6e6, rel=1e-3)  # issue: 184
    assert F.train_flops_per_token(conf, 8192) == 6.0 * active + attention \
        == pytest.approx(2.21e9, rel=5e-3)
    assert F.flash_train_bytes_per_token(conf) == 6 * 5 * 36 * 128 * 2
    assert F.flash_train_bytes_per_token(conf, kind=F.SLIDING) == \
        6 * 4 * 36 * 128 * 2
    assert F.flash_train_bytes_per_token(conf, kind=F.FULL) == 6 * 36 * 128 * 2
    # the grouped matmul: the held routed experts of the 4 sparse layers
    # alone, no shared expert, no dense layer
    T = 24576
    assert F.held_share(conf) == 0.125 and F.sparse_layers(conf) == 4
    assert F.expert_rows_per_step(conf, T) == T * 8 / 8 == T
    assert F.expert_gemm_flops_per_step(conf, T) == 4 * 9 * 2.0 * T * E * I
    assert F.expert_gemm_flops_per_step(conf, T, held=0.25) == \
        2 * F.expert_gemm_flops_per_step(conf, T)
    assert F.expert_gemm_bytes_per_step(conf, T) == \
        4 * 9.0 * (16 * E * I + T * (E + I)) * 2
    peak = M.load_peaks(ROOT)["TPU v5 lite"]
    t, bound = F.roofline_seconds(F.expert_gemm_flops_per_step(conf, T),
                                  F.expert_gemm_bytes_per_step(conf, T), peak)
    assert bound == "compute" and t > 0


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
@pytest.fixture()
def empty_registry():
    """The program's registry emptied for one test and put back after it:
    other modules keep handles to metrics they made at import (the goodput
    gauges), which a bare ``clear()`` would orphan for the tests that run
    later in the same process."""
    from deepspeed_tpu.telemetry import get_registry

    reg = get_registry()
    with reg._lock:
        kept = dict(reg._metrics)
        reg._metrics.clear()
    yield reg
    with reg._lock:
        reg._metrics.clear()
        reg._metrics.update(kept)


def test_bias_spread_from_the_programs_gauge(cell, manifest, empty_registry):
    from deepspeed_tpu.parallel.moe import record_stats

    obs = {"cell": cell}
    assert moe_expert_bias_spread.read(obs) is None     # no gauge: nothing
    counts = np.full((3, 8), 4)
    stats = {"tokens_per_expert": counts, "dropped": np.zeros(3),
             "balance_loss": np.ones(3), "router_z": np.ones(3)}
    record_stats(stats)                     # a program without a bias
    assert moe_expert_bias_spread.read(obs) is None
    bias = np.zeros((3, 8), np.float32)
    bias[0, :2] = (-0.004, 0.006)           # spreads 0.010, 0.002, 0.030
    bias[1, 5] = 0.002
    bias[2, :2] = (-0.010, 0.020)
    record_stats(dict(stats, expert_bias=bias))
    assert moe_expert_bias_spread.read(obs) == pytest.approx(0.010)
    # the last finished step's, not a sum
    record_stats(dict(stats, expert_bias=2 * bias))
    assert moe_expert_bias_spread.read(obs) == pytest.approx(0.020)
    # the held share reads this cell's columns 16-31 of 128
    a = np.zeros((4, 128))
    b = a + 192.0
    b[:, 16:32] = 384.0                     # 16 x 384 of 112 x 192 + 16 x 384
    held = {"cell": cell, moe_load_imbalance.COUNTER: [a, b]}
    assert moe_held_pair_pct.read(held) == pytest.approx(100 * 2 / 9)
    assert moe_held_pair_pct.of_the_window(held) == pytest.approx(2 / 9)
    # the older cells do not report the new metric
    for other in list(ACCEPTED)[:3]:
        c = M.load_cell(manifest, other, ROOT)
        assert "moe_expert_bias_spread" not in [m["name"] for m in c.per_layer]


# ----------------------------------------------------------------------
# the comparison sees each named fault
# ----------------------------------------------------------------------
class _Ctx:
    seed, rehearse = 3, True

    def __init__(self):
        self.notes, self.lines = [], []

    def log(self, msg):
        self.lines.append(msg)

    def check(self, ok, what):
        if not ok:
            self.notes.append(what)
        return bool(ok)


@pytest.fixture(scope="module")
def small(cell):
    """The driver's model at the rehearsal's widths on seeded weights, with
    the reference forward's normalised hidden states."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    driver, reference = cell.driver(), cell.reference()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    conf.update(cell.config["rehearse"])
    model, cfg = driver.model_config(conf)
    ids = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, cfg.max_position_embeddings)), jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    # at 64 wide a 0.02 initialiser gives scores near 0, a uniform softmax
    # and router scores that are ties: give the projections and the router
    # the scale they have at the published width
    for i in range(cfg.num_hidden_layers):
        layer = params[f"layers_{i}"]
        for name in ("q_proj_kernel", "k_proj_kernel", "gate_proj_kernel"):
            layer["self_attn"][name] = layer["self_attn"][name] * 12.0
        for norm in ("q_norm", "k_norm"):
            layer["self_attn"][norm]["scale"] = jnp.asarray(
                np.random.default_rng(i).uniform(0.5, 3.0, cfg.head_dim),
                jnp.float32)
        if "moe" in layer:
            layer["moe"]["gate"]["wg"] = layer["moe"]["gate"]["wg"] * 6.0
    ffn_in, attn_in = [], []
    reference.loss_parts(params, ids, ffn_inputs=ffn_in, attn_inputs=attn_in,
                         **driver.reference_kwargs(conf))
    assert len(ffn_in) == len(attn_in) == 5
    return driver, reference, cfg, conf, params, ffn_in, attn_in


@pytest.mark.parametrize("fault", [
    None, "rope_on_full", "no_rope_on_sliding", "no_gate", "qk_norm_whole",
    "window+1", "kv_mod"])
def test_attention_check_refuses_each_named_fault(small, fault, monkeypatch):
    driver, reference, cfg, conf, params, _, attn_in = small
    assert set(reference.FAULTS) == {
        "rope_on_full", "no_rope_on_sliding", "no_gate", "qk_norm_whole",
        "window+1", "kv_mod"}
    if fault is not None:
        real = reference.attention
        monkeypatch.setattr(
            reference, "attention",
            lambda kind, *a, **kw: real(kind, *a, **dict(kw, fault=fault)))
    ctx = _Ctx()
    driver.check_attention(ctx, cfg, conf, reference, params, attn_in)
    assert len(ctx.lines) == 2 and "sliding_attention" in ctx.lines[0] \
        and "full_attention" in ctx.lines[1]
    sliding = [n for n in ctx.notes if "sliding_attention" in n]
    full = [n for n in ctx.notes if "full_attention" in n]
    if fault is None:
        assert ctx.notes == []
    elif fault in ("no_rope_on_sliding", "window+1"):
        assert len(sliding) == 1 and not full
    elif fault == "rope_on_full":
        assert len(full) == 1 and not sliding
    else:
        assert len(sliding) == 1 and len(full) == 1


@pytest.mark.parametrize("fault", [
    None, "bias_ignored", "bias_in_weights", "softmax", "no_scale",
    "held_denominator", "no_shared"])
def test_expert_check_refuses_each_named_fault(small, fault, monkeypatch):
    driver, reference, cfg, conf, params, ffn_in, _ = small
    assert set(reference.EXPERT_FAULTS) == {
        "bias_ignored", "bias_in_weights", "softmax", "no_scale",
        "held_denominator", "no_shared"}
    if fault is not None:
        real = reference.expert_ffn
        monkeypatch.setattr(
            reference, "expert_ffn",
            lambda *a, **kw: real(*a, **dict(kw, fault=fault)))
    ctx = _Ctx()
    driver.check_experts(ctx, cfg, conf, reference, params, ffn_in)
    assert len(ctx.lines) == 1 and "seeded bias" in ctx.lines[0]
    assert len(ctx.lines[0].split("bias ")[1].split()) == 4   # sparse layers
    assert bool(ctx.notes) == (fault is not None)
    # the bias the comparison runs under is of the size of the scores'
    # spread, from the seed, and not zero
    b = driver.seeded_bias(3, 1, params["layers_1"]["moe"], ffn_in[1])
    assert b.shape == (16,) and 0.02 < b.std() < 0.5
    assert np.array_equal(b, driver.seeded_bias(3, 1, params["layers_1"]["moe"],
                                                ffn_in[1]))
    assert not np.array_equal(b, driver.seeded_bias(4, 1, params["layers_1"][
        "moe"], ffn_in[1]))


@pytest.mark.parametrize("control", [None, "fp8", "gate_up_swapped"])
def test_dense_check_sees_the_dense_layer_alone(small, monkeypatch, control):
    driver, reference, cfg, conf, params, ffn_in, _ = small
    assert reference.DENSE_FAULTS == ("gate_up_swapped",)
    if control is not None:
        real = reference.dense_ffn
        extra = {"operand_bits": (4, 3)} if control == "fp8" \
            else {"fault": control}
        monkeypatch.setattr(reference, "dense_ffn",
                            lambda p, h: real(p, h, **extra))
    ctx = _Ctx()
    driver.check_dense(ctx, cfg, conf, reference, params, ffn_in)
    assert len(ctx.lines) == 1 and "layer 0" in ctx.lines[0]
    assert bool(ctx.notes) == (control is not None)
    assert all("dense FFN" in note for note in ctx.notes)


def test_dense_check_runs_the_blocks_own_ffn(small, monkeypatch):
    """What comparison 3 executes is ``LlamaBlock._dense_ffn``, the method
    the window times under ``mlp_dense``: a fault put into THAT method (the
    activation on the wrong projection) is refused, and it is called once
    a dense layer."""
    import jax

    from deepspeed_tpu.models import llama

    driver, reference, cfg, conf, params, ffn_in, _ = small
    real, calls = llama.LlamaBlock._dense_ffn, []

    def swapped(self, h):
        calls.append(self.sparse)
        gate = llama._dense(h, cfg.intermediate_size, ("embed", "mlp"),
                            cfg=cfg, name="up_proj", module=self)
        up = llama._dense(h, cfg.intermediate_size, ("embed", "mlp"),
                          cfg=cfg, name="gate_proj", module=self)
        return llama._dense(jax.nn.silu(gate) * up, cfg.hidden_size,
                            ("mlp", "embed"), cfg=cfg, name="down_proj",
                            module=self)

    monkeypatch.setattr(llama.LlamaBlock, "_dense_ffn", swapped)
    ctx = _Ctx()
    driver.check_dense(ctx, cfg, conf, reference, params, ffn_in)
    assert calls == [False] and len(ctx.notes) == 1
    monkeypatch.setattr(llama.LlamaBlock, "_dense_ffn", real)
    ctx = _Ctx()
    driver.check_dense(ctx, cfg, conf, reference, params, ffn_in)
    assert ctx.notes == []


def test_bias_check_holds_the_program_to_the_references_update(small):
    """After a window each layer's bias is ``reference.bias_update`` over
    every step's counts, exactly; one step lost, or a bias a thousandth
    off at one expert, is refused."""
    import types

    driver, reference, cfg, conf, params, _, _ = small
    rate = conf["moe"]["bias_update_rate"]
    rng = np.random.default_rng(0)
    steps = [rng.integers(0, 50, (4, 16)) for _ in range(5)]
    want = np.zeros((4, 16), np.float32)
    for counts in steps:
        want = np.stack([reference.bias_update(c, b, rate)
                         for c, b in zip(counts, want)])
    trained = {f"layers_{i + 1}": {"moe": {"gate": {"expert_bias": want[i]}}}
               for i in range(4)}

    def engine(params, n):
        return types.SimpleNamespace(
            global_steps=n, drain_step_stats=lambda wait: None,
            state=types.SimpleNamespace(params=params))

    ctx = _Ctx()
    driver.check_bias(ctx, engine(trained, 5), cfg, conf, reference, steps)
    assert ctx.notes == [] and "5 steps x 4 layers" in ctx.lines[-1]
    ctx = _Ctx()                        # a step the driver did not see
    driver.check_bias(ctx, engine(trained, 6), cfg, conf, reference, steps)
    assert len(ctx.notes) == 1 and "6" in ctx.notes[0]
    off = {k: {"moe": {"gate": {"expert_bias": v["moe"]["gate"][
        "expert_bias"].copy()}}} for k, v in trained.items()}
    off["layers_3"]["moe"]["gate"]["expert_bias"][7] += rate
    ctx = _Ctx()
    driver.check_bias(ctx, engine(off, 5), cfg, conf, reference, steps)
    assert len(ctx.notes) == 1 and "layer 3" in ctx.notes[0] \
        and "1 of 16" in ctx.notes[0]


def test_rehearsal_of_the_trinity_cell_prints_a_correct_line():
    """The whole control flow on the CPU at tiny widths that keep the
    shape (a dense layer then four expert layers, 4 query heads on 2
    key-value heads, window 32 of 128, 4 of 16 experts held from expert 4,
    top-4, a shared expert, a bias the step moves)."""
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
    for check in ("reference check: engine loss", "expert check",
                  "dense check: layer 0",
                  "attention check: layer 0 (sliding_attention)",
                  "attention check: layer 3 (full_attention)",
                  "bias check:"):
        assert check in p.stderr
