"""The language-model loss with a per-token weight and an explicit
denominator (PR 40: block diffusion's ``sum_i mask_i / t_i nll_i / (B
L)``): the chunked head and the dense one against a hand-built sum,
weight 1 with the count as denominator against today's value bit for bit,
no gradient to the weights, and the unweighted rule traced as it was.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import common
from deepspeed_tpu.telemetry import get_registry

B, S, E, V, VP = 2, 48, 16, 50, 128


def _case(seed=0):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((B, S, E)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((VP, E)), jnp.float32)
    t = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32).at[0, :5].set(-100)
    wt = jnp.asarray(rng.random((B, S)) * (rng.random((B, S)) < 0.5),
                     jnp.float32)
    return h, w, t, wt


def _chunked(h, w, t, chunk=32, **kw):
    return common.chunked_lm_loss(h, w, t, vocab_size=V, padded_vocab_size=VP,
                                  chunk=chunk, dtype=jnp.float32, **kw)


def _by_hand(h, w, t, wt, denominator):
    lg = jnp.where(jnp.arange(VP) < V, h @ w.T, -jnp.inf)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, jnp.where(t < 0, 0, t)[..., None], -1)[..., 0]
    return (jnp.where(t < 0, 0.0, nll) * wt).sum() / denominator


@pytest.mark.parametrize("chunk", [32, 40, 4096])
def test_weight_one_and_the_count_reproduce_todays_value_bit_for_bit(chunk):
    h, w, t, _ = _case()
    count = (t != -100).sum()
    plain = jax.jit(jax.value_and_grad(lambda h, w: _chunked(h, w, t, chunk),
                                       (0, 1)))(h, w)
    ones = jax.jit(jax.value_and_grad(lambda h, w: _chunked(
        h, w, t, chunk, weights=jnp.ones((B, S)), denominator=count),
        (0, 1)))(h, w)
    assert plain[0] == ones[0]
    for a, b in zip(plain[1], ones[1]):
        assert bool((a == b).all())


@pytest.mark.parametrize("chunk", [32, 40, 4096])
def test_weighted_chunked_loss_and_gradients_match_a_hand_built_sum(chunk):
    h, w, t, wt = _case(1)
    got = jax.jit(jax.value_and_grad(lambda h, w: _chunked(
        h, w, t, chunk, weights=wt, denominator=float(B * S)), (0, 1)))(h, w)
    want = jax.jit(jax.value_and_grad(
        lambda h, w: _by_hand(h, w, t, wt, B * S), (0, 1)))(h, w)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_dense_cross_entropy_takes_the_same_weights():
    h, w, t, wt = _case(2)
    logits = jnp.where(jnp.arange(VP) < V, h @ w.T,
                       jnp.finfo(jnp.float32).min)
    got = common.cross_entropy_loss(logits, t, weights=wt,
                                    denominator=float(B * S))
    np.testing.assert_allclose(got, _by_hand(h, w, t, wt, B * S), rtol=1e-6)
    np.testing.assert_allclose(got, _chunked(h, w, t, weights=wt,
                                             denominator=float(B * S)),
                               rtol=1e-6)
    count = (t != -100).sum()
    assert common.cross_entropy_loss(logits, t) == common.cross_entropy_loss(
        logits, t, weights=jnp.ones((B, S)), denominator=count)


def test_a_zero_weight_takes_a_token_out_and_the_weights_get_no_gradient():
    h, w, t, wt = _case(3)
    zeroed = wt.at[1, 7].set(0.0)
    moved = h.at[1, 7].add(3.0)
    f = lambda h, wt: _chunked(h, w, t, weights=wt, denominator=float(B * S))
    assert f(h, zeroed) == f(moved, zeroed)
    assert f(h, wt.at[1, 7].set(1.0)) != f(moved, wt.at[1, 7].set(1.0))
    assert float(jnp.abs(jax.jit(jax.grad(f, 1))(h, wt)).max()) == 0.0
    dh = jax.jit(jax.grad(f, 0))(h, zeroed)
    assert float(jnp.abs(dh[1, 7]).max()) == 0.0


def test_the_unweighted_rule_is_traced_as_it_was():
    """No weight operand and no multiply in the jaxpr of the unweighted
    loss, and its three head products a chunk in the forward rule, none in
    the backward (``lm_head_products_total``)."""
    h, w, t, wt = _case(4)

    def products():
        entry = get_registry().snapshot().get("lm_head_products_total")
        return {s["labels"]["pass"]: s["value"]
                for s in (entry or {"samples": []})["samples"]}

    common._fused_ce.cache_clear()
    before = products()
    plain = jax.make_jaxpr(jax.grad(lambda h: _chunked(h, w, t)))(h)
    after = products()
    assert after.get("forward", 0) - before.get("forward", 0) == 3
    assert after.get("backward", 0) == before.get("backward", 0)
    weighted = jax.make_jaxpr(jax.grad(lambda h: _chunked(
        h, w, t, weights=wt, denominator=96.0)))(h)
    assert products().get("forward", 0) - after.get("forward", 0) == 3
    assert products().get("backward", 0) == before.get("backward", 0)
    # the weighted rule carries one operand more into its custom rule
    def operands(**kw):
        jaxpr = jax.make_jaxpr(lambda h: _chunked(h, w, t, **kw))(h)
        rule, = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name.startswith("custom_vjp")]
        return len(rule.invars)

    assert operands(weights=wt, denominator=96.0) == operands() + 1
