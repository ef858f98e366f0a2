"""``ops/short_conv.py`` (PR 45) against the explicit loop of
``benchmark/reference/lfm2.py``: forward and every gradient at 2, 3 and 4
taps in the XLA form and both types; rows of a batch independent; nothing
after position t reaches the output at t; the Pallas row kernels
(``ops/pallas/short_conv.py``, interpreted) over rows that span blocks, so
that both halos are read; what is refused; what is booked.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.ops.pallas import short_conv as kernel
from deepspeed_tpu.ops.short_conv import IMPLS, short_conv_rows

reference = load_module(ROOT, "reference", "lfm2")
B, S, C = 3, 40, 16


def _operands(L, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng([seed, L])
    bg, cg, u = (jnp.asarray(rng.normal(0, 1, (B, S, C)), dtype)
                 for _ in range(3))
    return bg, cg, u, jnp.asarray(rng.normal(0, 1, (C, L)), dtype)


def short_conv(bg, cg, u, w):
    """The XLA form on the three thirds side by side."""
    return short_conv_rows(jnp.concatenate([bg, cg, u], -1), w, "shift")


def _loop(bg, cg, u, w):
    """The reference's filter between the two gates, float32."""
    f = reference._f32
    return f(cg) * reference._filter(f(bg) * f(u), f(w), None, None)


def _by_hand(bg, cg, u, w):
    """The equation itself, a position at a time in numpy."""
    bg, cg, u, w = (np.asarray(t, np.float64) for t in (bg, cg, u, w))
    z, L = bg * u, w.shape[1]
    y = np.zeros_like(z)
    for t in range(S):
        for j in range(L):
            src = t - (L - 1) + j
            if src >= 0:
                y[:, t] += w[:, j] * z[:, src]
    return cg * y


@pytest.mark.parametrize("L", [2, 3, 4])
def test_forward_and_every_gradient_match_the_loop(L):
    ops = _operands(L)
    got = short_conv(*ops)
    np.testing.assert_allclose(got, _by_hand(*ops), atol=1e-5)
    np.testing.assert_allclose(got, _loop(*ops), atol=1e-5)
    probe = jnp.asarray(np.random.default_rng(9).normal(0, 1, (B, S, C)),
                        jnp.float32)
    grads = jax.grad(lambda *o: (short_conv(*o) * probe).sum(),
                     (0, 1, 2, 3))(*ops)
    want = jax.grad(lambda *o: (_loop(*o) * probe).sum(), (0, 1, 2, 3))(*ops)
    for name, g, r in zip(("dbg", "dcg", "du", "dw"), grads, want):
        np.testing.assert_allclose(g, r, atol=1e-4, err_msg=name)


def test_bf16_operands_give_a_bf16_result_near_the_float32_one():
    ops = _operands(3, jnp.bfloat16)
    got = short_conv(*ops)
    assert got.dtype == jnp.bfloat16 and got.shape == (B, S, C)
    want = _loop(*ops)          # the same rounded operands, float32 inside
    err = np.linalg.norm(np.asarray(got, np.float32) - want) \
        / np.linalg.norm(want)
    assert err < 4e-3, err      # one rounding of the result
    dw = jax.grad(lambda w: short_conv(*ops[:3], w).astype(
        jnp.float32).sum())(ops[3])
    assert dw.dtype == jnp.bfloat16 and np.isfinite(
        np.asarray(dw, np.float32)).all()


def test_rows_are_independent_and_nothing_comes_from_the_future():
    bg, cg, u, w = _operands(3)
    whole = short_conv(bg, cg, u, w)
    for b in range(B):          # a row alone is the row in the batch
        np.testing.assert_allclose(
            short_conv(bg[b:b + 1], cg[b:b + 1], u[b:b + 1], w),
            whole[b:b + 1], atol=1e-6)
    t = 17                      # everything after t changed: y[:t+1] stays
    noise = jnp.asarray(np.random.default_rng(4).normal(0, 1, (B, S, C)),
                        jnp.float32).at[:, :t + 1].set(0.0)
    moved = short_conv(bg + noise, cg + noise, u + noise, w)
    np.testing.assert_array_equal(moved[:, :t + 1], whole[:, :t + 1])
    assert np.abs(np.asarray(moved - whole)[:, t + 1:]).max() > 0.1
    # ... and the output at t reads exactly the last L positions
    jac = jax.jacobian(lambda u: short_conv(bg, cg, u, w)[0, t, 0])(u)
    reads = np.flatnonzero(np.abs(np.asarray(jac)[0, :, 0]) > 0)
    assert reads.tolist() == [t - 2, t - 1, t]


def test_a_sequence_shorter_than_the_filter():
    bg, cg, u, w = (t[:, :2] if t.ndim == 3 else t for t in _operands(4))
    np.testing.assert_allclose(short_conv(bg, cg, u, w),
                               _loop(bg, cg, u, w), atol=1e-6)


# ----------------------------------------------------------------------
# the row kernels, interpreted: rows of two and three blocks
# ----------------------------------------------------------------------
def _rows(L, S, dtype=jnp.float32, C=kernel.LANES, B=2):
    rng = np.random.default_rng([7, L, S])
    bcu = jnp.asarray(rng.normal(0, 1, (B, S, 3 * C)), dtype)
    w = jnp.asarray(rng.normal(0, 1, (C, L)), jnp.float32)
    probe = jnp.asarray(rng.normal(0, 1, (B, S, C)), jnp.float32)
    return bcu, w, probe


def _thirds(bcu):
    C = bcu.shape[-1] // 3
    return bcu[..., :C], bcu[..., C:2 * C], bcu[..., 2 * C:]


@pytest.mark.parametrize("L,S", [(2, 2 * kernel.BLOCK), (3, 3 * kernel.BLOCK),
                                 (4, 2 * kernel.BLOCK)])
def test_the_row_kernels_match_the_loop_across_blocks(L, S):
    bcu, w, probe = _rows(L, S)
    got = kernel.short_conv_rows(bcu, w, True)
    want = _loop(*_thirds(bcu), w)
    np.testing.assert_allclose(got, want, atol=2e-5)
    edges = [kernel.BLOCK - 1, kernel.BLOCK, kernel.BLOCK + 1, 0, S - 1]
    np.testing.assert_allclose(got[:, edges], want[:, edges], atol=2e-5)
    grads = jax.grad(lambda b, w: (kernel.short_conv_rows(
        b, w, True) * probe).sum(), (0, 1))(bcu, w)
    ref = jax.grad(lambda b, w: (_loop(*_thirds(b), w) * probe).sum(),
                   (0, 1))(bcu, w)
    assert grads[0].shape == bcu.shape and grads[1].shape == w.shape
    for name, g, r in zip(("d rows", "d taps"), grads, ref):
        err = np.abs(np.asarray(g - r)).max() / np.abs(np.asarray(r)).max()
        assert err < 1e-5, (name, err)
    # a row alone is the row in the batch: no halo crosses rows
    np.testing.assert_array_equal(
        kernel.short_conv_rows(bcu[1:], w, True), got[1:])


def test_the_row_kernels_round_once_from_float32():
    bcu, w, probe = _rows(3, 2 * kernel.BLOCK, jnp.bfloat16)
    got = kernel.short_conv_rows(bcu, w, True)
    assert got.dtype == jnp.bfloat16
    want = _loop(*_thirds(bcu), w)
    err = np.linalg.norm(np.asarray(got, np.float32) - want) \
        / np.linalg.norm(want)
    assert err < 4e-3, err
    other = np.asarray(short_conv_rows(bcu, w, "shift"), np.float32)
    assert np.linalg.norm(np.asarray(got, np.float32) - other) \
        / np.linalg.norm(other) < 4e-3     # the sum's order, one ulp
    db, dw = jax.grad(lambda b, w: (kernel.short_conv_rows(
        b, w, True).astype(jnp.float32) * probe).sum(),
        (0, 1))(bcu, w)
    assert db.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    rb, rw = jax.grad(lambda b, w: (_loop(*_thirds(b), w) * probe).sum(),
                      (0, 1))(bcu.astype(jnp.float32), w)
    assert np.linalg.norm(np.asarray(db, np.float32) - rb) \
        / np.linalg.norm(rb) < 4e-3
    # the cotangent arrives rounded to bf16; the sum over positions is float32
    assert np.linalg.norm(np.asarray(dw - rw)) / np.linalg.norm(rw) < 4e-3


def test_which_shapes_the_kernels_take():
    assert kernel.supported(8192, 2048, 3, jnp.bfloat16) is None
    assert "multiple of 512" in kernel.supported(8192, 640, 3, jnp.bfloat16)
    assert "multiple of 256" in kernel.supported(100, 512, 3, jnp.bfloat16)
    assert "taps" in kernel.supported(512, 512, 9, jnp.float32)
    assert "float16" in kernel.supported(512, 512, 3, jnp.float16)


def test_what_is_refused_and_what_is_booked():
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    bg, cg, u, w = _operands(3)
    bcu = jnp.concatenate([bg, cg, u], -1)
    with pytest.raises(ValueError, match="one of"):
        short_conv_rows(bcu, w, impl="conv")
    with pytest.raises(ValueError, match=r"\(B, S, 3C\) rows"):
        short_conv_rows(bcu[..., :-1], w)
    with pytest.raises(ValueError, match=r"\(C, L\) taps"):
        short_conv_rows(bcu, w.T)
    with pytest.raises(NotImplementedError, match="16 channels"):
        short_conv_rows(bcu, w, impl="pallas")      # asked for, not a shape
    assert IMPLS == ("auto", "pallas", "shift")

    def booked():
        return {(i, r): n for s, i, r, n in dispatch_report()
                if s == "short_conv"}

    before = booked()
    short_conv_rows(bcu, w)                         # a shape it cannot take
    big = _rows(3, kernel.BLOCK)
    short_conv_rows(big[0], big[1])                 # one it can: no TPU here
    short_conv_rows(bcu, w, "shift")
    after = booked()
    for key in (("shift", "16 channels are no multiple of 512"),
                ("shift", "no TPU"), ("shift", "impl='shift' asked for")):
        assert after[key] == before.get(key, 0) + 1, key
