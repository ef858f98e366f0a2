"""The router counts and picks with no scatter and no gather (PR 36):
``_count_ids`` against ``np.bincount``, ``topk_routing``'s weights against
``take_along_axis`` / ``lax.top_k``'s values bit for bit with their
gradients, and the compiled forward + backward of a share's block and of a
bias-routed one holding no ``scatter`` / ``gather`` under ``moe/route``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.parallel.moe import MoEConfig, _count_ids, topk_routing
from deepspeed_tpu.telemetry import device_scopes


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_lib.set_mesh(None)
    yield
    mesh_lib.set_mesh(None)


def _ids(kind, N, E):
    rng = np.random.default_rng(N + E)
    if kind == "elsewhere":         # a share's marker E among the ids
        return rng.integers(0, E + 1, N)
    if kind == "one_unchosen":
        return rng.integers(1, E, N)
    if kind == "all_on_one":
        return np.full(N, E - 1)
    return rng.integers(0, E, N)


@pytest.mark.parametrize("kind", ["even", "elsewhere", "one_unchosen",
                                  "all_on_one"])
@pytest.mark.parametrize("N,E", [(64, 8), (4096, 64), (1536, 128), (7, 3)])
def test_count_ids_is_bincount(N, E, kind):
    ids = _ids(kind, N, E).astype(np.int32)
    got = jax.jit(_count_ids, static_argnums=1)(jnp.asarray(ids), E)
    assert got.dtype == jnp.int32 and got.shape == (E,)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.bincount(ids[ids < E], minlength=E))
    if kind == "elsewhere":
        assert int(got.sum()) == int((ids < E).sum()) < N


def test_count_ids_compiles_to_a_reduce_and_no_scatter():
    text = jax.jit(_count_ids, static_argnums=1).lower(
        jax.ShapeDtypeStruct((4096,), jnp.int32), 64).compile().as_text()
    assert "reduce(" in text and "scatter" not in text


def _parents_weights(logits, bias, k, score_func, scale, norm=True):
    """``topk_routing``'s weights as the parent commit took them."""
    probs = jax.nn.softmax(logits, -1) if score_func == "softmax" \
        else jax.nn.sigmoid(logits)
    if bias is None:
        weights, _ = jax.lax.top_k(probs, k)
    else:
        _, experts = jax.lax.top_k(probs + jax.lax.stop_gradient(bias), k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    if norm:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return weights * scale


@pytest.mark.parametrize("score_func,biased", [
    ("sigmoid", True), ("softmax", True), ("softmax", False),
    ("sigmoid", False)])
def test_weights_and_their_gradient_are_the_gathered_ones_bit_for_bit(
        score_func, biased):
    S, E, k, scale = 256, 128, 8, 2.826
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(0, 2, (S, E)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.3, E), jnp.float32) if biased else None
    cot = jnp.asarray(rng.normal(size=(S, k)), jnp.float32)

    def ours(l, norm=True):
        return topk_routing(l, k, norm, score_func=score_func, bias=bias,
                            route_scale=scale)[0]

    def parents(l, norm=True):
        return _parents_weights(l, bias, k, score_func, scale, norm)

    def both(run, *args):
        out = [np.asarray(run(lambda l: f(l, *args))(logits))
               for f in (ours, parents)]
        grads = [np.asarray(run(jax.grad(
            lambda l: (f(l, *args) * cot).sum()))(logits))
            for f in (ours, parents)]
        assert np.abs(grads[1]).max() > 0
        return out, grads

    # op by op the whole of it is the parent's, renormalised and scaled
    out, grads = both(lambda f: f)
    np.testing.assert_array_equal(*out)
    np.testing.assert_array_equal(*grads)
    # compiled, the pick and its cotangent are; the renormalisation's sum
    # over k then fuses with another neighbour and may round another way
    out, grads = both(jax.jit, False)
    np.testing.assert_array_equal(*out)
    np.testing.assert_array_equal(*grads)
    out, grads = both(jax.jit)
    np.testing.assert_allclose(*out, rtol=1e-6, atol=0)
    np.testing.assert_allclose(*grads, rtol=0,
                               atol=1e-4 * np.abs(grads[1]).max())
    if biased:      # the bias picked: not the k largest scores everywhere
        assert not np.array_equal(np.asarray(ours(logits)), np.asarray(
            _parents_weights(logits, None, k, score_func, scale)))


def _block(kind):
    common = dict(vocab_size=160, hidden_size=32, num_hidden_layers=1,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  intermediate_size=40, moe_intermediate_size=24,
                  max_position_embeddings=48, loss_chunk=16,
                  scan_layers=False, remat=True, remat_prevent_cse=True,
                  remat_policy="dots_saveable+flash", vocab_pad_multiple=32)
    moe = dict(num_experts=4, top_k=4, drop_tokens=False,
               norm_topk_prob=True, expert_act="swiglu", routed_experts=8,
               first_expert=2)
    if kind == "mellum2":
        return LlamaConfig(**common, moe=MoEConfig(**moe, aux_loss_weight=0.1))
    return LlamaConfig(**common, moe=MoEConfig(
        **moe, aux_loss_weight=0.0, score_func="sigmoid", route_scale=2.826,
        bias_update_rate=0.02, num_shared_experts=1))


@pytest.mark.parametrize("kind", ["mellum2", "trinity"])
def test_no_scatter_or_gather_under_moe_route_in_the_compiled_block(kind):
    """The mechanism has no guard, so that it engages is read from the
    executable: forward, the fenced remat's second forward and backward
    of one block, every instruction by the scope the program gave it."""
    model = LlamaForCausalLM(_block(kind))
    ids = jnp.zeros((2, 48), jnp.int32)
    shapes = meta.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), ids)["params"])

    def loss(p, ids):
        out = model.apply({"params": p}, ids, labels=ids, deterministic=False)
        return out["loss"], out["stats"]

    compiled = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        shapes, ids).compile()
    scopes = device_scopes.instruction_scopes(compiled)
    route = {name: op for name, op in scopes.items()
             if device_scopes.scope_of(op, 99).endswith("moe/route")}
    passes = {device_scopes.pass_of(op) for op in route.values()}
    assert passes == {"forward", "recompute", "backward"}, passes
    assert any(op.endswith("/sort") for op in route.values())
    moved = {name: op for name, op in route.items()
             if any(word in part for word in ("scatter", "gather")
                    for part in (name, op.rsplit("/", 1)[-1]))}
    assert not moved, moved
