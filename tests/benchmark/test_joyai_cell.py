"""The benchmark's fifth cell, ``train-joyai-flash-8k-1chip`` (PR 38): its
names resolve to files, its configuration is the catalog row cut as the
guide allows, the parameter count from the program's own shapes is the
file's, its operation counts are what a hand computes, its new reader gives
nothing (and does not raise) where there is nothing to read, its comparison
refuses named faults, and its rehearsal passes on the CPU.  Host-only,
nothing timed.

The tests at the top replace the seven that pin the manifest to four cells
(``tests/conftest.py``: two of ``test_trinity_cell.py`` hold every
``reduced`` inside a set that lacks ``n_routed_experts``, five of
``test_hbm_readers.py`` hold the list of cells and the tail of the metrics
to their day).  They are **position-free**: what an accepted PR added is
a prefix of every list it was appended to, a cut is a count (depth, dense
depth, context, experts held, vocabulary) under whatever name the source's
config gives it and never a width, and an entry is found by its name: the
next configuration adds a line to ``ACCEPTED`` in a file of its own, or
nothing, and needs no copy of these.
"""
import copy
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import flops_joyai as F
from benchmark.harness import manifest as M
from benchmark.layer_metrics import mtp_loss_excess

ROOT = M.ROOT
CELL = "train-joyai-flash-8k-1chip"
CONFIG = "joyai-llm-flash-z3-8bit"
# what a cut may name (model-configs guide, section 4), under the names the
# sources' configs use: depth, leading dense depth, context, the experts
# held here, the vocabulary slice
CUTS = {"n_positions", "num_hidden_layers", "n_layer", "num_dense_layers",
        "first_k_dense_replace", "max_position_embeddings", "num_experts",
        "n_routed_experts", "num_local_experts", "vocab_size"}


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
    "train-trinity-mini-8k-1chip": (
        "trinity-mini-z3-8bit", "packed-8k-25k", 1,
        ["num_hidden_layers", "num_dense_layers", "num_experts",
         "vocab_size"]),
    CELL: (CONFIG, "packed-8k-16k", 1,
           ["num_hidden_layers", "n_routed_experts", "vocab_size"]),
}
# the per-layer metrics in the order they were accepted, each with the
# first accepted cell that reports it and the cells that never will (the
# kinds of attention are Mellum 2's and Trinity's alone)
METRICS = [
    ("train-xl-z3-1chip", [
        "train_step_ms", "train_mfu_pct", "flash_share_pct", "flash_roofline",
        "device_idle_pct.train", "train_host_ms", "train_input_ms",
        "train_dispatch_ms", "setup_trace_lower_s", "setup_backend_compile_s",
        "setup_init_params_s"]),
    ("train-olmoe-z3-1chip", ["expert_gemm_share_pct", "expert_gemm_roofline",
                              "moe_load_imbalance"]),
    ("train-mellum2-8k-1chip", ["flash_window_roofline",
                                "flash_full_roofline",
                                "flash_window_share_pct",
                                "moe_held_pair_pct"]),
    ("train-trinity-mini-8k-1chip", ["moe_expert_bias_spread"]),
    ("train-xl-z3-1chip", ["peak_hbm_gib", "step_temp_hbm_gib"]),
    (CELL, ["mtp_loss_excess"]),
]
LAYER_TYPED = ("flash_window_roofline", "flash_full_roofline",
               "flash_window_share_pct")
HBM = ["peak_hbm_gib", "step_temp_hbm_gib"]


def is_prefix(short, long) -> bool:
    return list(long[:len(short)]) == list(short)


def is_subsequence(short, long) -> bool:
    rest = iter(long)
    return all(x in rest for x in short)


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
        and is_a_width("q_lora_rank") and is_a_width("qk_rope_head_dim") \
        and is_a_width("v_head_dim") \
        and not any(is_a_width(k) for k in CUTS)


def test_every_accepted_cell_is_still_there_with_its_values(manifest):
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    for name, (config, traffic, chips, reduced) in ACCEPTED.items():
        w = cells[name]
        assert (w["config"], w["traffic"], w["chips"]) == (config, traffic,
                                                           chips)
        assert configs[config]["reduced"] == reduced
    order = list(ACCEPTED)
    assert is_prefix(order, [w["name"] for w in manifest["workloads"]])
    assert is_prefix([v[0] for v in ACCEPTED.values()],
                     [c["name"] for c in manifest["configs"]])
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert is_prefix(order, e2e["train_tokens_per_s_chip"]["workloads"])
    assert e2e["train_tokens_per_s_chip"]["bound"] == 0.01
    assert e2e["setup_s"]["bound"] == 0.1 and manifest["run_seconds"] == 50
    assert "workloads" not in e2e["setup_s"]


def test_no_metric_lost_a_cell(manifest):
    """Every metric lists the accepted cells from its first on, in the
    order they were accepted (less those that have nothing for it to
    read), and the metrics stand in the order THEY were accepted."""
    order = list(ACCEPTED)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for first, names in METRICS:
        since = order[order.index(first):]
        for name in names:
            want = [c for c in since
                    if not (c == CELL and name in LAYER_TYPED)
                    and not (name == "moe_expert_bias_spread"
                             and c not in ("train-trinity-mini-8k-1chip",
                                           CELL))]
            assert is_prefix(want, by_name[name]["workloads"]), name
            assert by_name[name]["moves"] in e2e
    assert is_prefix([n for _, names in METRICS for n in names],
                     [m["name"] for m in manifest["per_layer"]])


@pytest.mark.parametrize("cell_name", list(ACCEPTED))
def test_every_cell_loads_the_two_hbm_entries(manifest, cell_name):
    c = M.load_cell(manifest, cell_name, ROOT)
    mine = [m for m in c.per_layer if m["name"] in HBM]
    assert [m["name"] for m in mine] == HBM
    for m in mine:
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            "name": m["name"], "unit": "GiB", "better": "lower",
            "source": "program_counter", "layer": "trainer",
            "moves": "train_tokens_per_s_chip"}
        assert is_prefix(list(ACCEPTED), m["workloads"])
        assert callable(c.reader(m["name"]))


def test_each_cell_reports_what_came_before_it_and_its_own(manifest):
    """A cell's list is, in the manifest's order, every metric whose first
    cell is it or an older one - less the layer-typed ones where the cell
    names no layer types - and the two every cell gained."""
    order = list(ACCEPTED)
    for i, name in enumerate(order):
        got = [m["name"] for m in M.load_cell(manifest, name, ROOT).per_layer]
        older = [n for first, names in METRICS for n in names
                 if order.index(first) <= i]
        assert is_subsequence(got, older), name
        assert set(HBM) <= set(got) and "train_step_ms" in got, name
    mine = [m["name"] for m in M.load_cell(manifest, CELL, ROOT).per_layer]
    assert mine == [
        "train_step_ms", "train_mfu_pct", "flash_share_pct", "flash_roofline",
        "device_idle_pct.train", "train_host_ms", "train_input_ms",
        "train_dispatch_ms", "setup_trace_lower_s", "setup_backend_compile_s",
        "setup_init_params_s", "expert_gemm_share_pct",
        "expert_gemm_roofline", "moe_load_imbalance", "moe_held_pair_pct",
        "moe_expert_bias_spread", "peak_hbm_gib", "step_temp_hbm_gib",
        "mtp_loss_excess"]
    excess = next(m for m in manifest["per_layer"]
                  if m["name"] == "mtp_loss_excess")
    assert excess == {"name": "mtp_loss_excess", "unit": "nats",
                      "better": "lower", "source": "program_counter",
                      "layer": "trainer", "moves": "train_tokens_per_s_chip",
                      "workloads": [CELL]}


# ----------------------------------------------------------------------
# the configuration
# ----------------------------------------------------------------------
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}
STARRED = ("mtp_equations", "mtp_input", "mtp_loss_weight", "bias_update",
           "sequence_aux_loss", "initializer_range")


def test_the_configuration_file_is_the_catalog_row_cut_three_ways(cell):
    conf = cell.config
    differs = {k for k, v in PUBLISHED.items() if conf[k] != v}
    assert differs == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size"} == set(conf["reduced"])
    assert (conf["num_hidden_layers"], conf["n_routed_experts"],
            conf["vocab_size"]) == (5, 16, 16160)
    for key in conf["reduced"]:
        assert conf["published"][key] == PUBLISHED[key]
    assert not any(is_a_width(k) for k in conf["reduced"])
    # floors: the leading dense layer once and four of the layers that
    # follow it (all alike), >= 8 experts, >= 1/8 of the vocabulary
    assert conf["num_hidden_layers"] - conf["first_k_dense_replace"] == 4
    assert conf["n_routed_experts"] >= 8
    assert conf["vocab_size"] * 8 == 129280
    assert conf["num_nextn_predict_layers"] == 1
    assert "sixteen" in conf["stands_for"] and "16" in conf["stands_for"]
    # the program's names beside the source's, each restating it
    moe = conf["moe"]
    assert conf["num_experts"] == conf["n_routed_experts"] == 16
    assert conf["routed_experts"] == moe["routed_experts"] == 256
    assert conf["num_dense_layers"] == conf["first_k_dense_replace"] == 1
    assert moe["route_scale"] == conf["routed_scaling_factor"] == 2.5
    assert moe["score_func"] == conf["scoring_func"] == "sigmoid"
    assert moe["num_shared_experts"] == conf["n_shared_experts"] == 1
    assert moe["aux_loss_weight"] == 0.0 and moe["first_expert"] == 16
    assert 0.001 <= moe["bias_update_rate"] <= 0.02
    assert "0.001" in conf["assumed"]["bias_update"] and \
        str(moe["bias_update_rate"]) in conf["assumed"]["bias_update"]
    assert conf["model_options"]["mtp_loss_weight"] == 0.3
    # what the config has no key for is written out, each with its reason
    for star in STARRED:
        assert "the config has no key" in conf["assumed"][star], star
    for key in ("rope", "layout", "eos_token_id", "document_mask", "dropout",
                "cut", "recipe", "rows", "warmup_steps", "unused_keys"):
        assert len(conf["assumed"][key]) > 20, key
    assert "permutation" in conf["assumed"]["rope"]
    assert "before the main model's final norm" in \
        conf["assumed"]["mtp_input"]
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["eos_token_id"] == 16159 < conf["vocab_size"]
    assert conf["micro_per_device"] * cell.traffic["seq_len"] in (8192, 16384)
    tol = conf["reference_check"]
    assert 0 < tol["loss_abs_tol"] <= 0.02
    for key in ("expert_rel_tol", "dense_rel_tol", "attention_rel_tol",
                "mtp_rel_tol"):
        assert 0 < tol[key] < 0.1
    for why in ("reason", "expert_reason", "dense_reason",
                "attention_reason", "mtp_reason"):
        assert len(tol[why]) > 40
    assert conf["trace_names"] == {"flash": "^self_attn_mla$",
                                   "train_module": "^jit_step_fn$",
                                   "expert_gemm": "^t?gmm$"}


def test_the_driver_builds_the_model_from_the_file_as_data(cell):
    import jax

    sys.path.insert(0, ROOT)
    driver = cell.driver()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    model, cfg = driver.model_config(conf)
    assert type(cfg).__name__ == "LlamaConfig"
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_dense_layers,
            cfg.num_attention_heads, cfg.intermediate_size, cfg.expert_size,
            cfg.vocab_size, cfg.rope_theta, cfg.rms_norm_eps) == (
        2048, 5, 1, 32, 7168, 768, 16160, 32e6, 1e-6)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.rope_interleave) == (
        1536, 512, 128, 64, 128, True)
    assert cfg.num_nextn_predict_layers == 1 and cfg.mtp_loss_weight == 0.3
    assert not (cfg.mup_enabled or cfg.attn_gate or cfg.sandwich_norm
                or cfg.qk_norm or cfg.kinds)
    assert [cfg.sparse(i) for i in range(5)] == [False] + [True] * 4
    assert cfg.loss_chunk == 8192 and not cfg.scan_layers
    assert cfg.padded_vocab_size == 16256
    moe = cfg.moe
    assert (moe.num_experts, moe.routed, moe.first_expert, moe.top_k,
            moe.drop_tokens, moe.norm_topk_prob, moe.expert_act) == (
        16, 256, 16, 8, False, True, "swiglu")
    assert (moe.score_func, moe.route_scale, moe.num_shared_experts,
            moe.bias_update_rate, moe.aux_loss_weight) == (
        "sigmoid", 2.5, 1, conf["moe"]["bias_update_rate"], 0.0)
    kw = driver.reference_kwargs(conf)
    assert kw["first_expert"] == 16 and kw["routed_experts"] == 256
    assert kw["num_dense_layers"] == 1 and kw["route_scale"] == 2.5
    assert (kw["kv_lora_rank"], kw["qk_nope_head_dim"],
            kw["qk_rope_head_dim"], kw["v_head_dim"]) == (512, 128, 64, 128)
    # the parameters held here, from the program's own shapes: the file's
    ids = np.zeros((1, 128), np.int32)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), ids, labels=ids))["params"]
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    E = 2048
    attn = (E * 1536 + 1536 + 1536 * 32 * 192 + E * 576 + 512
            + 512 * 32 * 256 + 32 * 128 * E)
    assert attn == 26_345_472 + 2048
    norms = 2 * E
    dense = attn + norms + 3 * E * 7168
    sparse = attn + norms + E * 256 + 256 + 17 * 3 * E * 768
    mtp = 2 * E * E + 3 * E + sparse
    assert n == dense + 4 * sparse + mtp + 2 * 16256 * E + E == 680_834_304
    assert "680,834,304" in conf["published"]["parameters"]


def test_flops_against_hand_computed_numbers(cell):
    conf = cell.config
    E, H, I = 2048, 32, 768
    attn = (E * 1536 + 1536 * H * 192 + E * 576 + 512 * H * 256
            + H * 128 * E)
    assert F.attention_matmul_params(conf) == attn == 26_345_472
    sparse = E * 256 + (1 + 8 * 16 / 256) * 3 * E * I
    assert sparse == 524_288 + 1.5 * 4_718_592
    assert F.blocks(conf) == 6 and F.sparse_layers(conf) == 5
    active = (6 * attn + 3 * E * 7168 + 5 * sparse + 2 * E * E
              + 2 * 16160 * E)
    assert F.active_matmul_params(conf) == active
    assert active == pytest.approx(315e6, rel=5e-3)     # the issue's 315 M
    # twice the even share of pairs here: half an expert's worth more a
    # token a sparse block
    assert F.active_matmul_params(conf, held=0.125) == \
        active + 5 * 8 * 0.0625 * 3 * E * I
    full = sum(i + 1 for i in range(8192)) / 8192
    assert F.kept_keys_per_token(8192) == full == 4096.5
    # a kept key: 2 * (128 + 64) for the score, 2 * 128 for the value
    attention = 6 * 3 * 2.0 * H * 320 * full
    assert F.attention_flops_per_token(conf, 8192, 3) == attention
    assert F.causal_attention_flops_per_token(conf, 8192, 3) == attention
    assert attention == pytest.approx(1.51e9, rel=2e-3)  # the issue's 1.51 G
    total = F.train_flops_per_token(conf, 8192)
    assert total == 6.0 * active + attention == pytest.approx(3.40e9, rel=5e-3)
    assert attention / total == pytest.approx(0.444, abs=0.005)
    # bytes: one 64-lane rope key a token, not 32
    assert F.flash_train_bytes_per_token(conf) == \
        6 * 2 * (12 * H * 128 + 3 * H * 64 + 3 * 64)
    T = 16384
    assert F.held_share(conf) == 0.0625
    assert F.expert_rows_per_step(conf, T) == T * 8 / 16 == 8192
    assert F.expert_gemm_flops_per_step(conf, T) == 5 * 9 * 2.0 * 8192 * E * I
    assert F.expert_gemm_flops_per_step(conf, T, held=0.125) == \
        2 * F.expert_gemm_flops_per_step(conf, T)
    assert F.expert_gemm_bytes_per_step(conf, T) == \
        5 * 9.0 * (16 * E * I + 8192 * (E + I)) * 2
    peak = M.load_peaks(ROOT)["TPU v5 lite"]
    t, bound = F.roofline_seconds(F.expert_gemm_flops_per_step(conf, T),
                                  F.expert_gemm_bytes_per_step(conf, T), peak)
    assert bound == "compute" and t > 0
    t, bound = F.roofline_seconds(
        F.causal_attention_flops_per_token(conf, 8192, 3),
        F.flash_train_bytes_per_token(conf), peak)
    assert bound == "compute"


# ----------------------------------------------------------------------
# the reader
# ----------------------------------------------------------------------
@pytest.fixture()
def empty_registry():
    """The program's registry emptied for one test and put back after it
    (``test_trinity_cell.py``'s, for its reason)."""
    from deepspeed_tpu.telemetry import get_registry

    reg = get_registry()
    with reg._lock:
        kept = dict(reg._metrics)
        reg._metrics.clear()
    yield reg
    with reg._lock:
        reg._metrics.clear()
        reg._metrics.update(kept)


def test_the_excess_from_the_programs_gauges(cell, empty_registry):
    read = cell.reader("mtp_loss_excess")
    assert read.__module__.endswith("mtp_loss_excess")
    obs = {"cell": cell}
    assert read(obs) is None                    # a program without them
    empty_registry.gauge("lm_loss", "x").set(7.25)
    assert read(obs) is None                    # the block was skipped
    second = empty_registry.gauge("mtp_loss", "x", ("depth",))
    second.labels("2").set(9.0)
    assert read(obs) is None                    # another depth's alone
    second.labels("1").set(7.5)
    assert read(obs) == pytest.approx(0.25)
    assert mtp_loss_excess.read is not None


def test_the_model_books_both_gauges(empty_registry):
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    LlamaForCausalLM.record_step_stats({"lm_loss": np.float32(6.0),
                                        "mtp_loss": np.float32(6.5)})
    assert mtp_loss_excess.read({}) == pytest.approx(0.5)


def test_a_program_without_the_registry_gives_none(monkeypatch):
    import builtins

    real = builtins.__import__

    def refuse(name, *a, **k):
        if name.startswith("deepspeed_tpu.telemetry"):
            raise ImportError(name)
        return real(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", refuse)
    assert mtp_loss_excess.read({}) is None


# ----------------------------------------------------------------------
# the comparison, at the rehearsal's sizes
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def small(cell):
    """The driver's own pieces on a seeded tiny model: ``(driver,
    reference, cfg, conf, params, ids, the reference's forward)``."""
    import jax

    sys.path.insert(0, ROOT)
    driver, reference = cell.driver(), cell.reference()
    conf = {k: v for k, v in cell.config.items() if k != "rehearse"}
    conf.update(cell.config["rehearse"])
    model, cfg = driver.model_config(conf)
    ids = np.random.default_rng(0).integers(0, 512, (1, 128)).astype(np.int32)
    from flax.core import meta

    params = meta.unbox(model.init(jax.random.PRNGKey(0), ids,
                                   labels=ids)["params"])
    params = jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim >= 2 else a, params)
    ref = driver.reference_forward(reference, params, ids, conf)
    return driver, reference, cfg, conf, params, ids, ref


@pytest.mark.parametrize("fault", [None, "rope_on_nope", "halves_on_q",
                                   "halves_on_k", "scale_nope",
                                   "k_rope_next_position",
                                   "no_q_latent_norm", "no_kv_latent_norm"])
def test_attention_check_refuses_each_named_fault(small, fault):
    driver, reference, cfg, conf, params, ids, ref = small
    kw = driver.reference_kwargs(conf)
    leaves = driver.blocks(reference, params, cfg)
    for i in (1, len(leaves) - 1):      # a sparse block, the prediction's
        err = driver.read_attention(cfg, reference, leaves[i]["self_attn"],
                                    ref["attn_in"][i], kw,
                                    **({"fault": fault} if fault else {}))
        assert (err < 0.02) == (fault is None), (i, fault, err)


@pytest.mark.parametrize("fault", [None, "bias_ignored", "bias_in_weights",
                                   "softmax", "no_scale", "held_denominator",
                                   "no_shared"])
def test_expert_check_refuses_each_named_fault(small, fault):
    driver, reference, cfg, conf, params, ids, ref = small
    leaves = driver.blocks(reference, params, cfg)
    errs = driver.read_experts(3, cfg, conf, reference, leaves, ref["ffn_in"],
                               **({"fault": fault} if fault else {}))
    assert len(errs) == 3               # two sparse blocks, the prediction's
    assert (max(errs) < 0.02) == (fault is None), (fault, errs)


@pytest.mark.parametrize("fault", [None, "label_shift_1", "other_table",
                                   "other_head", "h_then_e"])
def test_prediction_block_check_refuses_each_named_fault(small, fault):
    driver, reference, cfg, conf, params, ids, ref = small
    got = driver.program_mtp_nll(cfg, params, ref["h"], ids)
    want = reference.mtp(ref["h"], ids, params, fault=fault,
                         **driver.reference_kwargs(conf))
    err = driver._rel_err(got, want)
    assert (err < 0.02) == (fault is None), (fault, err)


def test_bias_check_holds_every_biased_layer_to_the_references_update(small):
    driver, reference, cfg, conf, params, ids, ref = small
    rate = conf["moe"]["bias_update_rate"]
    steps = [np.random.default_rng(s).integers(0, 50, (3, 16))
             for s in range(4)]
    want = [np.zeros(16, np.float32) for _ in range(3)]
    for counts in steps:
        want = [reference.bias_update(c, b, rate)
                for c, b in zip(counts, want)]
    import jax

    moved = jax.tree_util.tree_map(lambda x: x, params)
    gates = [moved["layers_1"]["moe"]["gate"], moved["layers_2"]["moe"]["gate"],
             moved["mtp_0"]["block"]["moe"]["gate"]]
    for gate, b in zip(gates, want):
        gate["expert_bias"] = b
    notes = []
    ctx = types.SimpleNamespace(
        check=lambda ok, what: (ok or notes.append(what), ok)[1],
        log=lambda msg: None)
    engine = types.SimpleNamespace(
        state=types.SimpleNamespace(params=moved), global_steps=4,
        drain_step_stats=lambda wait: None)
    driver.check_bias(ctx, engine, cfg, conf, reference, steps)
    assert notes == []
    gates[2]["expert_bias"] = want[2] + np.float32(rate)   # the block's
    driver.check_bias(ctx, engine, cfg, conf, reference, steps)
    assert len(notes) == 1 and "biased layer 2" in notes[0]


def test_rehearsal_of_the_joyai_cell_prints_a_correct_line():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--rehearse"], capture_output=True, text=True,
        timeout=600, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, line
    assert line["compiles_in_window"] == 0 and line["attempted"] >= 1
    for said in ("reference check:", "attention check: prediction block",
                 "expert check:", "prediction-block check:", "dense check:",
                 "bias check:"):
        assert said in r.stderr, said
