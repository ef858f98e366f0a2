"""``telemetry/device_scopes.py``: the instruction → ``op_name`` map of a
kept executable, the generic scope and pass rules on ``op_name`` strings
recorded from all four cells' steps (rehearsal sizes, this sandbox), and
the reduction on a hand-made event list."""
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.telemetry import device_scopes, recompile, trace
from deepspeed_tpu.telemetry.registry import Registry

STEP = "jit(step_fn)/"
GPT2 = STEP + "transpose(jvp(GPT2LMHeadModel))/"
LLAMA = "jvp(LlamaForCausalLM)/"
BACK = STEP + "transpose(jvp(LlamaForCausalLM))/" + LLAMA + "checkpoint/"

# (op_name, scope at depth 3, pass); one block a model family
RECORDED = [
    # GPT-2-XL
    (STEP + "jvp(GPT2LMHeadModel)/embed/gather", "embed", "forward"),
    (STEP + "jvp(GPT2LMHeadModel)/h_17/attn/bhst,bthd->bshd/dot_general",
     "h_*/attn", "forward"),
    (GPT2 + "jvp(GPT2LMHeadModel)/checkpoint/h_0/ln_2/neg",
     "h_*/ln_*", "backward"),
    (GPT2 + "jvp(GPT2LMHeadModel)/checkpoint/rematted_computation/h_3/mlp/sub",
     "h_*/mlp", "recompute"),
    (GPT2 + "loss_head/while/body/closed_call/dot_general",
     "loss_head", "backward"),
    (GPT2 + "ln_f/neg", "ln_f", "backward"),
    (STEP + "optimizer/grad_clip/sqrt", "optimizer/grad_clip", "forward"),
    (STEP + "optimizer/jit(_where)/select_n", "optimizer", "forward"),
    (STEP + "add", "(step)", "forward"),
    ("state.opt_state[1][0].m_codes['h_0']['attn']['c_attn_bias']",
     "(step)", "forward"),
    # OLMoE
    (STEP + LLAMA + "layers_2/self_attn/q_norm/rsqrt",
     "layers_*/self_attn/q_norm", "forward"),
    (STEP + LLAMA + "layers_0/self_attn/bshd,bthd->bhst/dot_general",
     "layers_*/self_attn", "forward"),
    (STEP + LLAMA + "layers_1/moe/experts/moe/combine/skm,sk->sm/dot_general",
     "layers_*/moe/combine", "forward"),
    (BACK + "layers_1/moe/moe/route/gate/dot_general",
     "layers_*/moe/route", "backward"),
    (BACK + "rematted_computation/layers_2/moe/experts/moe/dispatch/"
     "jit(_take)/gather", "layers_*/moe/dispatch", "recompute"),
    (STEP + LLAMA + "layers_0/moe/experts/experts._weights/experts._weight/"
     "convert_element_type", "layers_*/moe/experts", "forward"),
    # Mellum 2
    (BACK + "layers_0/self_attn/rope/sliding_attention/mul",
     "layers_*/self_attn/rope", "backward"),
    (BACK + "layers_3/self_attn/self_attn_full/reduce_sum",
     "layers_*/self_attn/self_attn_full", "backward"),
    (BACK + "layers_1/self_attn/self_attn_window/bskgd,btkd->bkgst/transpose",
     "layers_*/self_attn/self_attn_window", "backward"),
    (BACK + "rematted_computation/layers_2/moe/moe/route/scatter-add",
     "layers_*/moe/route", "recompute"),
    (STEP + LLAMA + "layers_0/moe/experts/moe/experts/jit(silu)/mul",
     "layers_*/moe/experts", "forward"),
    (STEP + LLAMA + "norm/rsqrt", "norm", "forward"),
    # Trinity
    (STEP + "LlamaForCausalLM.update_state_leaves/moe/bias_update/sign",
     "moe/bias_update", "forward"),
    (BACK + "layers_0/mlp_dense/layers_0._dense_ffn/dot_general",
     "layers_*/mlp_dense", "backward"),
    (STEP + LLAMA + "layers_4/moe/moe/shared/shared/dot_general",
     "layers_*/moe/shared", "forward"),
    (BACK + "rematted_computation/layers_2/pre_mlp_norm/mul",
     "layers_*/pre_mlp_norm", "recompute"),
    (STEP + LLAMA + "layers_1/self_attn/attn/gate/exp",
     "layers_*/self_attn/attn", "forward"),
]


@pytest.mark.parametrize("op_name, scope, pass_", RECORDED,
                         ids=[r[0].rsplit(")/", 1)[-1][-48:] for r in RECORDED])
def test_scope_and_pass_of_recorded_op_names(op_name, scope, pass_):
    assert device_scopes.scope_of(op_name) == scope
    assert device_scopes.pass_of(op_name) == pass_


def test_scope_depth_folds_indices_and_takes_a_returning_name_back():
    op = BACK + "layers_13/moe/experts/moe/combine/jit(_take)/gather"
    assert device_scopes.scope_of(op, 1) == "layers_*"
    assert device_scopes.scope_of(op, 2) == "layers_*/moe"
    assert device_scopes.scope_of(op, 99) == "layers_*/moe/combine"
    # a call instruction: its own last segment is the wrapper
    assert device_scopes.scope_of(
        STEP + LLAMA + "layers_0/moe/experts/moe/dispatch/jit(_take)") == \
        "layers_*/moe/dispatch"
    gate = STEP + LLAMA + "layers_1/self_attn/attn/gate/exp"
    assert device_scopes.scope_of(gate, 4) == "layers_*/self_attn/attn/gate"


def test_instruction_scopes_of_a_tiny_step_with_a_device_span():
    def step(state, x):
        with trace.device_span("optimizer"):
            w = state["w"] - 0.1 * jnp.tanh(x @ state["w"]).T @ x
        with trace.device_span("loss_head"):
            loss = jnp.sum(w * w)
        return {"w": w}, loss

    f = recompile.RecompileWatchdog(registry=Registry()).watch(
        jax.jit(step, donate_argnums=(0,)), "unit.scopes", staged=True)
    f({"w": jnp.ones((32, 32))}, jnp.ones((8, 32)))
    scopes = device_scopes.instruction_scopes(f.compiled)
    assert scopes and all(isinstance(k, str) and v for k, v in scopes.items())
    # every named instruction is one of the executable's own
    text = f.compiled.as_text()
    assert all(f"{name} = " in text for name in scopes)
    by_scope = {device_scopes.scope_of(op) for op in scopes.values()}
    assert {"optimizer", "loss_head"} <= by_scope
    assert any(op.endswith("optimizer/dot_general") for op in scopes.values())
    # parsed once an executable, kept beside the handle
    assert device_scopes.instruction_scopes(f.compiled) is scopes


def test_scope_table_on_a_hand_made_list_with_a_nested_while_body():
    ms = 1e6
    scopes = {
        "fusion.1": STEP + LLAMA + "layers_0/moe/moe/route/gate/dot_general",
        "sort.2": BACK + "rematted_computation/layers_1/moe/moe/route/sort",
        "fusion.3": BACK + "layers_1/moe/moe/route/mul",
        "while.4": STEP + LLAMA + "loss_head/while",
        "fusion.5": STEP + LLAMA + "loss_head/while/body/closed_call/"
                                   "dot_general",
        "adam8bit.6": STEP + "optimizer/pallas_call",
    }
    one_step = [
        ("fusion.1", 0 * ms, 4 * ms),
        ("while.4", 10 * ms, 10 * ms),       # spans its body's two runs
        ("fusion.5", 11 * ms, 3 * ms),
        ("fusion.5", 15 * ms, 3 * ms),
        ("copy.7", 21 * ms, 1 * ms),         # no op_name
        ("copy.8", 22 * ms, 1 * ms),
        ("sort.2", 30 * ms, 2 * ms),
        ("fusion.3", 32 * ms, 6 * ms),
        ("adam8bit.6", 40 * ms, 5 * ms),
    ]
    events = one_step + [(n, s + 100 * ms, d) for n, s, d in one_step]
    table = device_scopes.scope_table(events, scopes, steps=2,
                                      top=("moe/route",))
    rows = {(r["scope"], r["pass"]): r["ms_a_step"] for r in table["scopes"]}
    assert rows == {
        ("layers_*/moe/route", "forward"): pytest.approx(4.0),
        ("layers_*/moe/route", "recompute"): pytest.approx(2.0),
        ("layers_*/moe/route", "backward"): pytest.approx(6.0),
        # the while's self time and its body's: 4 + 6, nothing twice
        ("loss_head", "forward"): pytest.approx(10.0),
        ("optimizer", "forward"): pytest.approx(5.0),
    }
    assert table["scopes"][0]["scope"] == "loss_head"      # largest first
    assert table["no_op_name"] == [{"kind": "copy",
                                    "ms_a_step": pytest.approx(2.0)}]
    assert table["device_ms_a_step"] == pytest.approx(29.0)
    assert sum(rows.values()) + 2.0 == pytest.approx(
        table["device_ms_a_step"])
    heavy = table["top"]["moe/route"]["instructions"]
    assert [(r["instruction"], r["pass"]) for r in heavy] == [
        ("fusion.3", "backward"), ("fusion.1", "forward"),
        ("sort.2", "recompute")]
    assert heavy[1]["scope"] == "layers_*/moe/route/gate"
    assert heavy[0]["ms_a_step"] == pytest.approx(6.0)
    assert [r["op"] for r in heavy] == ["mul", "dot_general", "sort"]
    assert table["top"]["moe/route"]["ops"] == [
        {"op": "mul", "ms_a_step": pytest.approx(6.0)},
        {"op": "dot_general", "ms_a_step": pytest.approx(4.0)},
        {"op": "sort", "ms_a_step": pytest.approx(2.0)}]


def test_capture_on_a_backend_without_a_device_plane_gives_nothing():
    ran = []
    events = device_scopes.capture(
        lambda: ran.append(jax.block_until_ready(jnp.ones(4) + 1)))
    assert ran and events == {}


def test_profile_device_scopes_needs_a_step_that_ran_and_a_chip():
    import deepspeed_tpu
    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config
    import numpy as np

    mesh_mod.set_mesh(None)
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2LMHeadModel(gpt2_config("gpt2-tiny",
                                              dtype=jnp.float32)),
            config={"train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "steps_per_print": 10**9})
        engine.init_params()
        ids = np.zeros((engine.train_batch_size, 16), np.int32)

        def batches():
            while True:
                yield {"input_ids": ids, "labels": ids}

        with pytest.raises(RuntimeError, match="train_batch once first"):
            engine.profile_device_scopes(batches(), steps=1)
        engine.train_batch(data_iter=batches())
        steps = engine.global_steps
        with pytest.raises(RuntimeError, match="no /device:TPU plane"):
            engine.profile_device_scopes(batches(), steps=2)
        assert engine.global_steps == steps + 2      # it trained them
        scopes = device_scopes.instruction_scopes(engine.compiled_step())
        assert {"optimizer", "loss_head"} <= {
            device_scopes.scope_of(op, 1) for op in scopes.values()}
    finally:
        mesh_mod.set_mesh(None)
