"""``runtime/grad_origin.py``: a gradient that is only an exact up-cast of
what the backward computed comes back in that dtype; every other one, and
every value, is ``jax.value_and_grad``'s."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.runtime.grad_origin import narrow_grads

BF = jnp.bfloat16


def _dense(w, x):
    return jnp.dot(x, w.astype(BF))


def _layer(p, x):
    h = _dense(p["cast"], x)
    # float32 arithmetic on an uncast leaf: a norm's scale, a router
    return (h.astype(jnp.float32) * p["uncast"]).astype(BF)


def _plain(p, x):
    return _layer(p, x)


def _remat(p, x):
    return jax.checkpoint(_layer)(p, x)


def _jit(p, x):
    return jax.jit(_layer)(p, x)


def _tied(p, x):
    # the same leaf cast twice: its gradient is a sum of two cotangents
    return _dense(p["cast"], _layer(p, x))


def _transposed(p, x):
    return jnp.dot(_layer(p, x), p["cast"].astype(BF).T)


def _scanned(p, x):
    stack = {k: jnp.stack([v, v * 0.5]) for k, v in p.items()}

    def body(h, layer):
        return _layer(layer, h), None

    # the stack is built from the leaves, so the leaves' own gradients are
    # sums over the layers; the stacked form is what a scanned model holds
    return jax.lax.scan(body, x, stack)[0]


def _stacked_leaves(p, x):
    def body(h, layer):
        return _layer(layer, h), None

    return jax.lax.scan(body, x, p)[0]


CASES = {
    # name: (forward, stacked leaves?, {leaf: narrow dtype or None})
    "plain": (_plain, False, {"cast": BF, "uncast": None}),
    "remat": (_remat, False, {"cast": BF, "uncast": None}),
    "jit": (_jit, False, {"cast": BF, "uncast": None}),
    "tied": (_tied, False, {"cast": None, "uncast": None}),
    "transposed": (_transposed, False, {"cast": None, "uncast": None}),
    "sum-over-scan": (_scanned, False, {"cast": None, "uncast": None}),
    "scan-stacked": (_stacked_leaves, True, {"cast": BF, "uncast": None}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_gradients_come_back_in_the_dtype_the_backward_wrote(case):
    forward, stacked, want = CASES[case]
    rng = np.random.default_rng(0)
    p = {"cast": jnp.asarray(rng.normal(size=(16, 16)), jnp.float32),
         "uncast": jnp.asarray(rng.normal(size=(16,)), jnp.float32)}
    if stacked:
        p = {k: jnp.stack([v, v * 0.5]) for k, v in p.items()}
    x = jnp.asarray(rng.normal(size=(4, 16)), BF)
    traces = []

    def loss(p):
        traces.append(1)
        out = forward(p, x).astype(jnp.float32)
        return jnp.sum(out * out), {"mean": out.mean()}

    (l0, aux0), g0 = jax.jit(jax.value_and_grad(loss, has_aux=True))(p)
    del traces[:]
    def narrowed(p):
        out, grads = jax.value_and_grad(loss, has_aux=True)(p)
        return out, narrow_grads(grads)

    (l1, aux1), g1 = jax.jit(narrowed)(p)
    assert len(traces) == 1            # the model is traced once
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    np.testing.assert_allclose(aux0["mean"], aux1["mean"], rtol=1e-6)
    for k, dt in want.items():
        assert g1[k].dtype == (dt or jnp.float32), (k, g1[k].dtype)
        # narrow or not, no value moved
        np.testing.assert_array_equal(np.asarray(g0[k]),
                                      np.asarray(g1[k], np.float32))
