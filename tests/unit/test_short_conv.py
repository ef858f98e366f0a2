"""``ops/short_conv.py`` (PR 45) against the explicit loop of
``benchmark/reference/lfm2.py``: forward and every gradient at 2, 3 and 4
taps in the XLA form and both types; rows of a batch independent; nothing
after position t reaches the output at t; the Pallas row kernels
(``ops/pallas/short_conv.py``, interpreted) over rows that span blocks, so
that both halos are read, in the gated form and (PR 51) the ungated one with
its activation; the gated kernels' jaxpr held to the parent's; what is
refused; what is booked.
"""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.ops.pallas import short_conv as kernel
from deepspeed_tpu.ops.short_conv import (IMPLS, causal_conv_rows,
                                          short_conv_rows)

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
    grads = jax.jit(jax.grad(lambda *o: (short_conv(*o) * probe).sum(),
                             (0, 1, 2, 3)))(*ops)
    want = jax.jit(jax.grad(lambda *o: (_loop(*o) * probe).sum(),
                            (0, 1, 2, 3)))(*ops)
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
    dw = jax.jit(jax.grad(lambda w: short_conv(*ops[:3], w).astype(
        jnp.float32).sum()))(ops[3])
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
# the row kernels, interpreted: rows of two and three blocks, in the gated
# form (LFM2's ``[Bg ; Cg ; u]``) and the ungated one with its activation
# (the Gated DeltaNet's ``[q ; k ; v]``, PR 51): one body, ``FORMS`` apart
# ----------------------------------------------------------------------
def _plain(activation):
    """The interpreter makes the approximate reciprocal of the kernels'
    sigmoid by rounding to bfloat16, so the Newton step after it leaves 2^-17
    of the value where the chip leaves what the division does (``chip_smoke.py
    kernel_short_conv`` holds that): 20 times the room with silu."""
    act = jax.nn.silu if activation == "silu" else (lambda c: c)
    f = reference._f32
    return (lambda x, w: kernel.causal_conv_rows(x, w, activation, True),
            lambda x, w: act(reference._filter(f(x), f(w), None, None)), 1,
            20 if activation else 1)


# form: (the interpreted kernels, the float32 loop, thirds of a row, room)
FORMS = {"gated": (lambda bcu, w: kernel.short_conv_rows(bcu, w, True),
                   lambda bcu, w: _loop(*_thirds(bcu), w), 3, 1),
         "silu": _plain("silu"), "plain": _plain(None)}


def _rows(L, S, dtype=jnp.float32, C=kernel.LANES, B=2, thirds=3):
    rng = np.random.default_rng([7, L, S])
    rows = jnp.asarray(rng.normal(0, 1, (B, S, thirds * C)), dtype)
    w = jnp.asarray(rng.normal(0, 1, (C, L)), jnp.float32)
    probe = jnp.asarray(rng.normal(0, 1, (B, S, C)), jnp.float32)
    return rows, w, probe


def _thirds(bcu):
    C = bcu.shape[-1] // 3
    return bcu[..., :C], bcu[..., C:2 * C], bcu[..., 2 * C:]


@pytest.mark.parametrize("form,L,S,C", [
    ("gated", 2, 2 * kernel.BLOCK, 512), ("gated", 3, 3 * kernel.BLOCK, 512),
    ("gated", 4, 2 * kernel.BLOCK, 512), ("silu", 4, 3 * kernel.BLOCK, 512),
    ("plain", 3, 2 * kernel.BLOCK, 512), ("silu", 1, 2 * kernel.BLOCK, 512),
    # whole lane tiles, no multiple of 512 (PR 52): three chunks of 384 lanes
    ("silu", 4, 2 * kernel.BLOCK, 1152)])
def test_the_row_kernels_match_the_loop_across_blocks(form, L, S, C):
    run, loop, thirds, room = FORMS[form]
    rows, w, probe = _rows(L, S, C=C, thirds=thirds)
    got = run(rows, w)
    want = loop(rows, w)
    np.testing.assert_allclose(got, want, atol=2e-5 * room)
    edges = [kernel.BLOCK - 1, kernel.BLOCK, kernel.BLOCK + 1, 0, S - 1]
    np.testing.assert_allclose(got[:, edges], want[:, edges],
                               atol=2e-5 * room)
    grads = jax.jit(jax.grad(lambda r, w: (run(r, w) * probe).sum(),
                             (0, 1)))(rows, w)
    ref = jax.jit(jax.grad(lambda r, w: (loop(r, w) * probe).sum(),
                           (0, 1)))(rows, w)
    assert grads[0].shape == rows.shape and grads[1].shape == w.shape
    for name, g, r in zip(("d rows", "d taps"), grads, ref):
        err = np.abs(np.asarray(g - r)).max() / np.abs(np.asarray(r)).max()
        assert err < 1e-5 * room, (name, err)
    # a row alone is the row in the batch: no halo crosses rows; and what
    # lies after a block's second position changes nothing up to it
    t = kernel.BLOCK + 1
    alone = run(rows[1:].at[:, t + 1:].add(1.0), w)
    np.testing.assert_array_equal(alone[:, :t + 1], got[1:, :t + 1])
    assert np.abs(np.asarray(alone - got[1:])[:, t + 1]).max() > 1e-3


@pytest.mark.parametrize("form,L", [("gated", 3), ("silu", 4)])
def test_the_row_kernels_round_once_from_float32(form, L):
    run, loop, thirds, _ = FORMS[form]
    rows, w, probe = _rows(L, 2 * kernel.BLOCK, jnp.bfloat16, thirds=thirds)
    got = run(rows, w)
    assert got.dtype == jnp.bfloat16
    want = loop(rows, w)
    err = np.linalg.norm(np.asarray(got, np.float32) - want) \
        / np.linalg.norm(want)
    assert err < 4e-3, err
    shift = short_conv_rows(rows, w, "shift") if form == "gated" \
        else causal_conv_rows(rows, w, form, "shift")
    other = np.asarray(shift, np.float32)
    assert np.linalg.norm(np.asarray(got, np.float32) - other) \
        / np.linalg.norm(other) < 4e-3     # the sum's order, one ulp
    db, dw = jax.jit(jax.grad(lambda r, w: (run(r, w).astype(jnp.float32)
                                            * probe).sum(), (0, 1)))(rows, w)
    assert db.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    rb, rw = jax.jit(jax.grad(lambda r, w: (loop(r, w) * probe).sum(),
                              (0, 1)))(rows.astype(jnp.float32), w)
    assert np.linalg.norm(np.asarray(db, np.float32) - rb) \
        / np.linalg.norm(rb) < 4e-3
    # the cotangent arrives rounded to bf16; the sum over positions is float32
    assert np.linalg.norm(np.asarray(dw - rw)) / np.linalg.norm(rw) < 4e-3


def test_which_shapes_the_kernels_take():
    assert kernel.supported(8192, 2048, 3, jnp.bfloat16) is None
    assert kernel.supported(8192, 8192, 4, jnp.bfloat16) is None
    assert "multiple of 512" in kernel.supported(8192, 640, 3, jnp.bfloat16)
    assert "multiple of 256" in kernel.supported(100, 512, 3, jnp.bfloat16)
    assert "taps" in kernel.supported(512, 512, 9, jnp.float32)
    assert "float16" in kernel.supported(512, 512, 3, jnp.float16)
    # the gated rows go whole; the ungated channels by blocks that divide them
    assert kernel._grid(4, 8192, 2048, True) == ((4, 32), 2048)
    assert kernel._grid(3, 8192, 8192, False) == ((3, 32, 4), 2048)
    assert kernel._grid(2, 512, 2560, False) == ((2, 2, 5), 512)
    # ... and by the widest lanes that divide a block (Olmo-Hybrid's 11,520
    # channels of [q ; k ; v] are 90 lane tiles, 22.5 x 512)
    assert kernel.supported(8192, 11520, 4, jnp.bfloat16, gated=False) is None
    assert "multiple of 512" in kernel.supported(8192, 11520, 4, jnp.bfloat16)
    assert "multiple of 128" in kernel.supported(8192, 11456, 4, jnp.bfloat16,
                                                 gated=False)
    assert kernel._grid(2, 8192, 11520, False) == ((2, 32, 6), 1920)
    assert [kernel._width(c) for c in (2048, 1920, 1152, 1280, 640)] \
        == [512, 384, 384, 256, 128]


def gated_trace():
    """The jaxpr of the gated kernels, forward and ``jax.vjp``, over two lane
    blocks and two row blocks, with each block's index map (the jaxpr's text
    leaves them out) and without source positions."""
    B, S, C, L = 2, 2 * kernel.BLOCK, 2 * kernel.LANES, 3
    shapes = (jax.ShapeDtypeStruct((B, S, 3 * C), jnp.bfloat16),
              jax.ShapeDtypeStruct((C, L), jnp.float32),
              jax.ShapeDtypeStruct((B, S, C), jnp.bfloat16))

    def run(bcu, w, dy):
        out, vjp = jax.vjp(lambda b, w: kernel.short_conv_rows(b, w, False),
                           bcu, w)
        return (out,) + vjp(dy)

    closed = jax.make_jaxpr(run)(*shapes)
    text = [str(closed)]
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            text += [str(m.index_map_jaxpr)
                     for m in eqn.params["grid_mapping"].block_mappings]
    return re.sub(r" at /\S+:\d+", "", "\n".join(text))


def test_the_gated_kernels_trace_to_the_parents_jaxpr():
    """LFM2's instance of the body is what commit ``bf2df90`` (the parent of
    PR 51, which gave the body its ungated form) traces: equations, shapes,
    block specs and index maps, scratch, names, cost estimate.  The digest
    was computed there with this function.  ONE body holds both forms; the
    gates and the activation are static options of it."""
    text = gated_trace()
    assert text.count("pallas_call") == 2 and "short_conv_rows_back" in text
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "3d28cad5c145f5c766c701915a6af75daba90e89831b4c189c9212aab1afb7d9")


def _booked():
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    return {(i, r): n for s, i, r, n in dispatch_report()
            if s == "short_conv"}


def test_what_is_refused_and_what_is_booked():
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

    before = _booked()
    short_conv_rows(bcu, w)                         # a shape it cannot take
    big = _rows(3, kernel.BLOCK)
    short_conv_rows(big[0], big[1])                 # one it can: no TPU here
    short_conv_rows(bcu, w, "shift")
    after = _booked()
    for key in (("shift", "16 channels are no multiple of 512"),
                ("shift", "no TPU"), ("shift", "impl='shift' asked for")):
        assert after[key] == before.get(key, 0) + 1, key


def test_what_the_ungated_call_refuses_and_books():
    """The ungated filter goes through the same plan: ``shift`` with the
    guard that refused, ``pallas`` (on one device; interpreted here) with a
    reason that says it is the ungated form, its rows, channels, taps and
    activation."""
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.comm.mesh import build_mesh

    x, _, _, w = _operands(4)
    with pytest.raises(ValueError, match="one of"):
        causal_conv_rows(x, w, "silu", impl="conv")
    with pytest.raises(NotImplementedError, match="16 channels"):
        causal_conv_rows(x, w, "silu", impl="pallas")
    rows, taps, _ = _rows(4, kernel.BLOCK, thirds=1)
    with pytest.raises(NotImplementedError, match="refused the mesh"):
        causal_conv_rows(rows, taps, impl="pallas")     # eight devices, none
    before = _booked()
    causal_conv_rows(x, w)
    causal_conv_rows(rows, taps, None)
    causal_conv_rows(rows, taps, "silu", "shift")
    mesh_lib.set_mesh(build_mesh({"dp": 1}, devices=jax.devices()[:1]))
    try:
        got = causal_conv_rows(rows, taps, "silu", "pallas", interpret=True)
        jax.eval_shape(lambda x, w: causal_conv_rows(x, w, None, "pallas"),
                       rows, taps)
    finally:
        mesh_lib.set_mesh(None)
    np.testing.assert_allclose(
        got, causal_conv_rows(rows, taps, "silu", "shift"), atol=4e-4)
    after = _booked()
    for key, n in ((("shift", "16 channels are no multiple of 128"), 1),
                   (("shift", "no TPU"), 1),
                   (("shift", "impl='shift' asked for"), 2),
                   (("pallas", "ungated, rows 256 x 512, 4 taps, silu; "
                               "one device"), 1),
                   (("pallas", "ungated, rows 256 x 512, 4 taps, no "
                               "activation; one device"), 1)):
        assert after[key] == before.get(key, 0) + n, key
