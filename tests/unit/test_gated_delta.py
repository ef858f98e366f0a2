"""The gated delta rule in chunks (``ops/gated_delta.py``, PR 48) against
the recurrence one position a step (``benchmark/reference/qwen3next.py
delta_rule``), forward and every gradient; the fused kernels
(``ops/pallas/gated_delta.py``, PR 49: preparation and scan in one body) in
the interpreter against the XLA form and the recurrence; the ungated causal
filter (``ops/short_conv.py causal_conv_rows``) against a loop over its
taps.  Since PR 52 also key heads of ``dk`` and value heads of ``dv``
channels (states ``dk x dv``, neither a multiple of 128 lanes: the kernels
read them in lane slots) under ``beta`` in (0, 2), against
``benchmark/reference/olmo_hybrid.py delta_rule``, and the 128 x 128 case
against the parent's kernels kept as ``_gated_delta_parent.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_module
from deepspeed_tpu.ops import gated_delta as ops
from deepspeed_tpu.ops.gated_delta import (_solve_unit_lower,
                                           gated_delta_rule)
from deepspeed_tpu.ops.pallas.spmd import dispatch_report
from deepspeed_tpu.ops.short_conv import causal_conv_rows
from deepspeed_tpu.telemetry import get_registry

reference = load_module(ROOT, "reference", "qwen3next")
wide = load_module(ROOT, "reference", "olmo_hybrid")    # dk != dv
NAMES = ("q", "k", "v", "g", "beta")


def _inputs(B, S, Hk, Hv, d, seed=0, g_scale=0.5):
    rng = np.random.default_rng(seed)

    def unit(x, H):
        x = x.reshape(B, S, H, d)
        return (x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)).reshape(
            B, S, H * d)

    q = unit(rng.standard_normal((B, S, Hk * d)), Hk) * d ** -0.5
    k = unit(rng.standard_normal((B, S, Hk * d)), Hk)
    v = rng.standard_normal((B, S, Hv * d))
    g = -np.abs(rng.standard_normal((B, S, Hv))) * g_scale
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((B, S, Hv))))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def _recurrence(q, k, v, g, beta):
    B, S, Hv = g.shape
    d = v.shape[-1] // Hv
    r = Hv // (k.shape[-1] // d)
    q, k = (jnp.repeat(x.reshape(B, S, -1, d), r, axis=2) for x in (q, k))
    return reference.delta_rule(q, k, v.reshape(B, S, Hv, d), g,
                                beta).reshape(B, S, Hv * d)


def _chunked(dtype, chunk):
    def run(q, k, v, g, beta):
        return gated_delta_rule(q.astype(dtype), k.astype(dtype),
                                v.astype(dtype), g, beta,
                                chunk=chunk).astype(jnp.float32)
    return run


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("chunk,Hk,Hv,dtype,tol", [
    (16, 2, 2, jnp.float32, 2e-5), (8, 1, 2, jnp.bfloat16, 2e-2),
])
def test_the_chunked_form_is_the_recurrence_forward_and_backward(
        chunk, Hk, Hv, dtype, tol):
    args = _inputs(2, 64, Hk, Hv, 8)
    probe = jnp.asarray(np.random.default_rng(9).standard_normal(
        (2, 64, Hv * 8)), jnp.float32)
    run = _chunked(dtype, chunk)
    got = jax.jit(jax.value_and_grad(
        lambda *a: (run(*a) * probe).sum(), range(5)))(*args)
    want = jax.jit(jax.value_and_grad(
        lambda *a: (_recurrence(*a) * probe).sum(), range(5)))(*args)
    assert abs(float(got[0]) - float(want[0])) < tol * 64 * 8
    for name, a, b in zip(NAMES, got[1], want[1]):
        assert _rel(a, b) < tol, name


RUN_64 = jax.jit(_chunked(jnp.float32, 64))


def test_a_chunk_of_64_takes_the_solve_through_its_four_blocks():
    args = _inputs(2, 128, 2, 2, 16)
    assert _rel(RUN_64(*args), _recurrence(*args)) < 2e-5


# one shape for the forward-only cases below: one executable a side
SHAPE = (2, 64, 2, 4, 8)
RUN = jax.jit(_chunked(jnp.float32, 16))
RECURRENCE = jax.jit(_recurrence)


@pytest.mark.parametrize("corner", ["g=0", "beta=1", "fast_decay"])
def test_the_corners_of_the_gates(corner):
    """No decay (the plain delta rule), a full write every token, and a
    decay that underflows inside one chunk (exp(gamma) reaches 0: nothing
    is divided by it)."""
    q, k, v, g, beta = _inputs(
        *SHAPE, g_scale=40.0 if corner == "fast_decay" else 0.5)
    if corner == "g=0":
        g = jnp.zeros_like(g)
    if corner == "beta=1":
        beta = jnp.ones_like(beta)
    got = RUN(q, k, v, g, beta)
    assert np.isfinite(np.asarray(got)).all()
    assert _rel(got, RECURRENCE(q, k, v, g, beta)) < 2e-5
    if corner == "fast_decay":
        grads = jax.jit(jax.grad(
            lambda *a: _chunked(jnp.float32, 16)(*a).sum(),
            range(5)))(q, k, v, g, beta)
        assert all(np.isfinite(np.asarray(x)).all() for x in grads)


def test_rows_are_independent_and_positions_causal():
    args = _inputs(*SHAPE)
    out = RUN(*args)
    # a row alone gives what it gives beside another: no state crosses rows
    swapped = RUN(*(x[::-1] for x in args))
    np.testing.assert_allclose(out[1], swapped[0], atol=1e-6)
    alone = RUN(*(jnp.concatenate([x[1:], x[1:] * 0.0 + 0.3]) for x in args))
    np.testing.assert_allclose(out[1], alone[0], atol=1e-6)
    # what follows position 40 changes nothing before it, across a chunk
    # boundary too
    later = [x.at[:, 40:].set(x[:, 40:] * 0.5 + 0.1) for x in args[:3]]
    moved = RUN(*later, *args[3:])
    np.testing.assert_array_equal(np.asarray(out[:, :40]),
                                  np.asarray(moved[:, :40]))
    assert np.abs(np.asarray(out[:, 40:] - moved[:, 40:])).max() > 1e-3


def test_correlated_keys_do_not_cancel():
    """Keys that are nearly one direction make ``A`` nearly all ones below
    the diagonal, where a product form of ``(I + A)^-1`` loses everything
    in float32; the substitution does not."""
    q, k, v, g, beta = _inputs(2, 128, 2, 2, 16)
    k = k * 0.05 + jnp.ones_like(k) / 4.0       # |k| ~ 1, all alike
    g, beta = jnp.zeros_like(g), jnp.ones_like(beta) * 0.99
    assert _rel(RUN_64(q, k, v, g, beta),
                RECURRENCE(q, k, v, g, beta)) < 1e-3


def test_the_triangular_solve_against_numpy():
    rng = np.random.default_rng(0)
    a = np.tril(rng.standard_normal((3, 32, 32)), -1).astype(np.float32)
    rhs = rng.standard_normal((3, 32, 5)).astype(np.float32)
    want = np.linalg.solve(np.eye(32) + a.astype(np.float64), rhs)
    np.testing.assert_allclose(_solve_unit_lower(jnp.asarray(a),
                                                 jnp.asarray(rhs)),
                               want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kw,said", [
    (dict(chunk=48), "no whole chunks of 48"),
    (dict(impl="triton"), "one of"),
])
def test_what_the_rule_refuses(kw, said):
    with pytest.raises(ValueError, match=said):
        gated_delta_rule(*_inputs(1, 64, 2, 2, 16), **kw)
    q, k, v, g, beta = _inputs(1, 64, 2, 2, 16)
    with pytest.raises(ValueError, match="no multiple of 2 key heads"):
        gated_delta_rule(q, k, jnp.concatenate([v, v[..., :16]], -1),
                         jnp.concatenate([g, g[..., :1]], -1),
                         jnp.concatenate([beta, beta[..., :1]], -1))


def _bf16(args):
    return [x.astype(jnp.bfloat16) if i < 3 else x for i, x in enumerate(args)]


def _fused(chunk, fused=True):
    """Past the plan (eight CPU devices and no mesh): the rule itself, by the
    kernels in the interpreter, or by XLA where ``fused`` is None."""
    return jax.jit(lambda *a: ops._rule(*a, chunk, fused).astype(jnp.float32))


@pytest.mark.parametrize("Hk,Hv", [(2, 2), (1, 2)])
def test_the_kernels_in_the_interpreter_are_the_xla_form(Hk, Hv):
    """``impl="pallas"`` at one small shape, two rows, one or two value
    heads a key head of 128 channels, 8 chunks of 32 (two grid steps a
    head-sequence, so the states and their cotangents cross a step's edge
    both ways; two 16-row blocks a chunk, joined once): the forward and the
    five gradients are the XLA form's to the rounding of bf16 operands, and
    as near the per-token recurrence as it is."""
    from deepspeed_tpu.ops.pallas import gated_delta as kernel

    assert kernel.supported(8, 32, 128, 128, jnp.bfloat16, Hv // Hk) is None
    args = _bf16(_inputs(2, 256, Hk, Hv, 128))
    probe = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, 256, Hv * 128)), jnp.float32)

    def run(rule):
        return jax.jit(jax.value_and_grad(lambda *a: (
            rule(*a).astype(jnp.float32) * probe).sum(), range(5)))

    (want, want_g), (got, got_g) = (run(_fused(32, how))(*args)
                                    for how in (None, True))
    assert abs(float(got) - float(want)) < 2e-3 * abs(float(want))
    exact = run(_recurrence)(*(x.astype(jnp.float32) for x in args))[1]
    for name, a, b, c in zip(NAMES, got_g, want_g, exact):
        assert _rel(a, b) < 6e-3, name
        assert _rel(a, c) < max(6e-3, 1.2 * _rel(b, c)), name


@pytest.mark.parametrize("corner", ["g=0", "beta=1", "fast_decay",
                                    "correlated_keys"])
def test_the_corners_of_the_gates_in_the_kernels(corner):
    """The gate corners of the XLA form above, and keys that are nearly one
    direction, through the kernels at chunks of 64 (four 16-row blocks,
    joined twice): the forward against the recurrence on the same bf16
    inputs and against the XLA form; under a decay that underflows inside a chunk every cotangent
    stays finite."""
    q, k, v, g, beta = _inputs(
        1, 256, 1, 2, 128, g_scale=40.0 if corner == "fast_decay" else 0.5)
    if corner == "g=0":
        g = jnp.zeros_like(g)
    if corner == "beta=1":
        beta = jnp.ones_like(beta)
    if corner == "correlated_keys":
        k = k * 0.05 + jnp.ones_like(k) / 128 ** 0.5
        g, beta = jnp.zeros_like(g), jnp.ones_like(beta) * 0.99
    args = _bf16((q, k, v, g, beta))
    got = _fused(64)(*args)
    assert np.isfinite(np.asarray(got)).all()
    exact = [x.astype(jnp.float32) for x in args]
    want, by_xla = _recurrence(*exact), _fused(64, None)(*args)
    # bf16 operands: as near the recurrence as the XLA form is
    assert _rel(got, want) < max(1e-2, 1.1 * _rel(by_xla, want))
    assert _rel(got, by_xla) < 6e-3
    if corner == "fast_decay":
        grads = jax.jit(jax.grad(lambda *a: _fused(64)(*a).sum(),
                                 range(5)))(*args)
        assert all(np.isfinite(np.asarray(x, np.float32)).all()
                   for x in grads)


def test_rows_are_independent_in_the_kernels():
    """A row beside another gives what it gives alone, to the bit: no state,
    gate or block index of the kernels crosses rows or key heads."""
    args = _bf16(_inputs(2, 128, 2, 4, 128, seed=7))
    run = _fused(32)
    out = run(*args)
    swapped = run(*(x[::-1] for x in args))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(swapped[0]))
    alone = run(*(x[1:] for x in args))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(alone[0]))
    assert np.abs(np.asarray(out[0] - out[1])).max() > 1e-3


def test_the_inverse_in_the_kernels_against_numpy():
    """``(I + A)^-1`` as the kernels build it (16-row diagonal blocks by
    their nilpotent product, then joined in pairs) for 2, 4 and 8 blocks."""
    from deepspeed_tpu.ops.pallas import gated_delta as kernel

    rng = np.random.default_rng(1)
    for C in kernel.CHUNKS:
        a = np.tril(rng.standard_normal((C, C)), -1).astype(np.float32) * 0.3
        got = jax.jit(lambda a: kernel._inverses([a], kernel._Masks(C))[0])(a)
        want = np.linalg.inv(np.eye(C) + a.astype(np.float64))
        np.testing.assert_allclose(got, want, rtol=2e-3,
                                   atol=2e-3 * np.abs(want).max())


@pytest.mark.parametrize("n,chunk,d,dtype,r,said", [
    (8, 32, 128, jnp.float32, 1, "operands of float32"),
    (8, 32, 512, jnp.bfloat16, 4, "4 states of 512 x 512 float32"),
    (8, 16, 128, jnp.bfloat16, 1, "chunks of 16 positions"),
    (8, 256, 128, jnp.bfloat16, 1, "chunks of 256 positions"),
    (6, 32, 128, jnp.bfloat16, 1, "6 chunks are no whole groups of 4"),
    (8, 32, 128, jnp.bfloat16, 8, "8 value heads a key head"),
])
def test_what_the_kernels_refuse_falls_to_xla_and_says_why(n, chunk, d, dtype,
                                                           r, said):
    from deepspeed_tpu.ops.pallas import gated_delta as kernel

    assert said in kernel.supported(n, chunk, d, d, dtype, r)
    args = [x.astype(dtype) if i < 3 else x
            for i, x in enumerate(_inputs(1, n * chunk, 1, r, d))]
    with pytest.raises(NotImplementedError, match=said):
        gated_delta_rule(*args, chunk=chunk, impl="pallas")
    jax.eval_shape(lambda *a: gated_delta_rule(*a, chunk=chunk), *args)
    assert any(site == "gated_delta" and impl == "xla" and said in why
               for site, impl, why, _ in dispatch_report())


def test_the_plan_names_the_fused_form_and_its_tile(monkeypatch):
    """On one device the plan takes the kernels and says what a grid step
    holds; the reason is the counter's label."""
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.comm.mesh import build_mesh

    mesh_lib.set_mesh(build_mesh({"dp": 1}, devices=jax.devices()[:1]))
    before = {r[:3]: r[3] for r in dispatch_report()}
    try:
        jax.eval_shape(lambda *a: gated_delta_rule(
            *a, chunk=64, impl="pallas", interpret=True),
            *_bf16(_inputs(1, 256, 1, 2, 128)))
    finally:
        mesh_lib.set_mesh(None)
    assert [r[1:3] for r in dispatch_report() if r[0] == "gated_delta"
            and r[3] > before.get(r[:3], 0)] == [(
                "pallas", "4 chunks of 64 x 1 key heads x 2 value heads of "
                "128, fused; one device")]


def test_the_dispatch_and_the_chunks_are_booked():
    def chunks():
        family = get_registry().snapshot().get("gated_delta_chunks_total")
        return {s["labels"]["pass"]: s["value"]
                for s in (family["samples"] if family else ())}

    before = chunks()
    args = _inputs(1, 64, 1, 1, 16, seed=5)
    jax.make_jaxpr(jax.grad(
        lambda *a: gated_delta_rule(*a, chunk=16).sum()))(*args)
    after = chunks()
    assert after["fwd"] - before.get("fwd", 0) >= 4     # 64 / 16 a trace
    assert after["bwd"] - before.get("bwd", 0) == 8     # again, and back
    assert any(site == "gated_delta" and impl == "xla" and n
               for site, impl, _, n in dispatch_report())


# ----------------------------------------------------------------------
# dk != dv (PR 52)
# ----------------------------------------------------------------------
def _wide_inputs(B, S, Hk, Hv, dk, dv, seed=0):
    """As :func:`_inputs` at key heads of ``dk`` and value heads of ``dv``
    channels, ``beta = 2 sigmoid(.)`` drawn so that a third lies above 1.5
    and ``g`` small enough that a state lives for tens of positions."""
    rng = np.random.default_rng(seed)

    def unit(x, H, d):
        x = x.reshape(B, S, H, d)
        return (x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)).reshape(
            B, S, H * d)

    q = unit(rng.standard_normal((B, S, Hk * dk)), Hk, dk) * dk ** -0.5
    k = unit(rng.standard_normal((B, S, Hk * dk)), Hk, dk)
    v = rng.standard_normal((B, S, Hv * dv))
    g = -np.abs(rng.standard_normal((B, S, Hv))) * 0.1
    beta = 2.0 / (1.0 + np.exp(-rng.standard_normal((B, S, Hv)) * 2.0 - 0.3))
    assert 0.2 < (beta > 1.5).mean() < 0.6 and beta.max() > 1.9
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def _wide_recurrence(Hk):
    def run(q, k, v, g, beta):
        B, S, Hv = g.shape
        r = Hv // Hk
        q, k = (jnp.repeat(x.reshape(B, S, Hk, -1), r, axis=2)
                for x in (q, k))
        return wide.delta_rule(q, k, v.reshape(B, S, Hv, -1), g,
                               beta).reshape(v.shape)
    return run


def _value_and_grads(rule, probe):
    return jax.jit(jax.value_and_grad(lambda *a: (
        rule(*a).astype(jnp.float32) * probe).sum(), range(5)))


@pytest.mark.parametrize("Hk,Hv,dk,dv", [(2, 2, 12, 24), (1, 2, 20, 8)])
def test_the_xla_form_at_unequal_widths_is_the_recurrence(Hk, Hv, dk, dv):
    """Float32, chunks of 16, ``beta`` in (0, 2) with a third above 1.5, 1
    and 2 value heads a key head: the forward and all five cotangents, and
    through the public call (``key_heads``), which books the state's shape."""
    args = _wide_inputs(2, 64, Hk, Hv, dk, dv)
    probe = jnp.asarray(np.random.default_rng(9).standard_normal(
        (2, 64, Hv * dv)), jnp.float32)
    got = _value_and_grads(lambda *a: gated_delta_rule(
        *a, chunk=16, key_heads=Hk), probe)(*args)
    want = _value_and_grads(_wide_recurrence(Hk), probe)(*args)
    assert abs(float(got[0]) - float(want[0])) < 2e-5 * 64 * dv
    for name, a, b in zip(NAMES, got[1], want[1]):
        assert _rel(a, b) < 2e-5, name
    family = get_registry().snapshot()["gated_delta_state_elems"]
    assert {(s["labels"]["dk"], s["labels"]["dv"]): s["value"]
            for s in family["samples"]}[(str(dk), str(dv))] == dk * dv
    with pytest.raises(ValueError, match="key_heads 7"):
        gated_delta_rule(*args, chunk=16, key_heads=7)


@pytest.mark.parametrize("Hk,Hv,dk,dv", [(1, 1, 24, 136), (1, 2, 40, 24)])
def test_the_kernels_take_heads_that_are_no_lane_tiles_in_slots(Hk, Hv, dk,
                                                                dv):
    """The kernels in the interpreter at key and value heads of different
    widths, neither a multiple of the 128 lanes (slots of 128 x 256 and of
    128 x 128), ``beta`` in (0, 2), 1 and 2 value heads a key head, 4 chunks
    of 32: the forward and the five cotangents are the XLA form's to the
    rounding of bf16 operands and as near the per-token recurrence as it
    is; nothing of a slot's zeros comes back."""
    from deepspeed_tpu.ops.pallas import gated_delta as kernel

    assert kernel.supported(4, 32, dk, dv, jnp.bfloat16, Hv // Hk) is None
    assert (kernel._slot(dk), kernel._slot(dv)) == (128, -(-dv // 128) * 128)
    args = _bf16(_wide_inputs(1, 128, Hk, Hv, dk, dv, seed=4))
    probe = jnp.asarray(np.random.default_rng(3).standard_normal(
        (1, 128, Hv * dv)), jnp.float32)

    def rule(how):
        return lambda *a: ops._rule(*a, 32, how, Hk)

    (want, want_g), (got, got_g) = (
        _value_and_grads(rule(how), probe)(*args) for how in (None, True))
    assert abs(float(got) - float(want)) < 4e-3 * abs(float(want))
    exact = _value_and_grads(_wide_recurrence(Hk), probe)(
        *(x.astype(jnp.float32) for x in args))[1]
    for name, a, b, c in zip(NAMES, got_g, want_g, exact):
        assert a.shape == c.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < 8e-3, name
        assert _rel(a, c) < max(8e-3, 1.2 * _rel(b, c)), name


def test_square_heads_of_128_trace_to_the_parents_kernels_bit_for_bit():
    """``train-qwen3next-gdn-8k-1chip``'s rule (16 key heads x 2 value heads
    of 128, chunks of 64) traces to the same program as the parent's
    kernels (``_gated_delta_parent.py``: before ``(dk, dv)`` and the slots),
    forward, saved states and backward, primitive for primitive with the
    same blocks, grid and cost; and at a small shape of the same kind the
    outputs are the parent's to the bit."""
    import re

    from deepspeed_tpu.ops.pallas import gated_delta as kernel

    from . import _gated_delta_parent as parent

    def traced(mod, shapes, **kw):
        def both(q, k, v, g, beta, do):
            return (mod.forward(q, k, v, g, beta, **kw),
                    mod.backward(q, k, v, g, beta, do, **kw))
        text = str(jax.make_jaxpr(both)(*shapes))
        return re.sub(r" at /[^ \n]*|name=\w+", "", text)

    def shapes(B, S, Hk, Hv, d):
        return tuple(jax.ShapeDtypeStruct(s, t) for s, t in (
            ((B, S, Hk * d), jnp.bfloat16), ((B, S, Hk * d), jnp.bfloat16),
            ((B, S, Hv * d), jnp.bfloat16), ((B, S, Hv), jnp.float32),
            ((B, S, Hv), jnp.float32), ((B, S, Hv * d), jnp.bfloat16)))

    cell = shapes(1, 8192, 16, 32, 128)
    assert traced(kernel, cell, chunk=64) == traced(parent, cell, chunk=64)
    assert traced(kernel, cell, chunk=64, key_heads=16) \
        == traced(parent, cell, chunk=64)
    args = _bf16(_inputs(1, 128, 1, 2, 128, seed=11))
    do = jnp.asarray(np.random.default_rng(12).standard_normal(
        (1, 128, 256)), jnp.bfloat16)
    for got, want in zip(
            kernel.backward(*args, do, chunk=32, interpret=True)
            + (kernel.forward(*args, chunk=32, interpret=True),),
            parent.backward(*args, do, chunk=32, interpret=True)
            + (parent.forward(*args, chunk=32, interpret=True),)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize("L,activation", [(4, "silu"), (3, None), (1, "silu")])
def test_the_ungated_filter_against_a_loop_over_taps(L, activation):
    rng = np.random.default_rng(L)
    x = rng.standard_normal((2, 12, 8)).astype(np.float32)
    w = rng.standard_normal((8, L)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(12):
        for j in range(L):
            src = t - (L - 1) + j       # the last tap is the current position
            if src >= 0:
                want[:, t] += w[:, j] * x[:, src]
    if activation == "silu":
        want = want / (1.0 + np.exp(-want))
    got = causal_conv_rows(jnp.asarray(x), jnp.asarray(w), activation)
    np.testing.assert_allclose(got, want, atol=1e-5)
    bf = causal_conv_rows(jnp.asarray(x, jnp.bfloat16), jnp.asarray(w),
                          activation)
    assert bf.dtype == jnp.bfloat16 and _rel(bf, want) < 2e-2
    with pytest.raises(ValueError, match="'silu' or None"):
        causal_conv_rows(jnp.asarray(x), jnp.asarray(w), "gelu")
    with pytest.raises(ValueError, match=r"\(B, S, C\) rows"):
        causal_conv_rows(jnp.asarray(x), jnp.asarray(w[:4]))


# ----------------------------------------------------------------------
# a decay a KEY CHANNEL (Kimi Delta Attention, PR 58): g (B, S, Hv, dk)
# ----------------------------------------------------------------------
kda = load_module(ROOT, "reference", "ling3")
LOWER = -5.0            # kda_lower_bound: the deepest log-decay a position


def _channel_inputs(B, S, Hk, Hv, d, seed=0, pinned=True):
    """As :func:`_inputs` with ``g`` (B, S, Hv, d) in (LOWER, 0); ``pinned``:
    over the WHOLE second chunk of 64 a quarter of the channels sit at the
    bound and a quarter at 0 (``exp(Gamma)`` underflows float32 on the
    first while ``exp(-Gamma)`` overflows it: a two-sided form's case),
    and across the first chunk's edge half of them."""
    q, k, v, _, beta = _inputs(B, S, Hk, Hv, d, seed)
    rng = np.random.default_rng(seed + 100)
    g = LOWER / (1.0 + np.exp(-2.0 * rng.standard_normal((B, S, Hv, d))))
    if pinned:
        g[:, 64:128, :, :d // 4] = LOWER
        g[:, 64:128, :, d // 4:d // 2] = 0.0
        g[:, 56:72, :, d // 2:] = LOWER
    return q, k, v, jnp.asarray(g, jnp.float32), beta


def _channel_recurrence(q, k, v, g, beta):
    B, S, Hv, dk = g.shape
    r = Hv // (k.shape[-1] // dk)
    q, k = (jnp.repeat(x.reshape(B, S, -1, dk), r, axis=2) for x in (q, k))
    return kda.kda_rule(q, k, v.reshape(B, S, Hv, -1), g,
                        beta).reshape(v.shape)


@pytest.mark.parametrize("Hk,Hv,dtype,tol", [
    (2, 2, jnp.float32, 2e-5), (1, 2, jnp.float32, 2e-5),
    (2, 2, jnp.bfloat16, 3e-2),
])
def test_a_decay_a_channel_is_the_recurrence_with_decays_at_the_bound(
        Hk, Hv, dtype, tol):
    """Two rows of three chunks of 64 (four solve blocks each): decays
    pinned at the bound and at 0 over a whole chunk, across a chunk's edge,
    and a second row that must start from zeros; forward and every
    gradient."""
    args = _channel_inputs(2, 192, Hk, Hv, 16)
    probe = jnp.asarray(np.random.default_rng(9).standard_normal(
        (2, 192, Hv * 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = _value_and_grads(_chunked(dtype, 64), probe)(*args)
        want = _value_and_grads(_channel_recurrence, probe)(*args)
        out = _chunked(dtype, 64)(*args)
    assert np.isfinite(np.asarray(out)).all()
    assert _rel(out, _channel_recurrence(*args)) < tol
    assert abs(float(got[0]) - float(want[0])) < tol * (
        1 + abs(float(want[0])))
    for name, a, b in zip(NAMES, got[1], want[1]):
        assert a.shape == b.shape, name
        # in bf16 the decay's gradient is a difference of rounded sums
        assert _rel(a, b) < (tol if dtype == jnp.float32 or name != "g"
                             else 0.15), name


def test_a_two_sided_decay_overflows_where_the_blocks_do_not():
    """``(K exp(Gamma)) (K exp(-Gamma))^T`` over a whole chunk: with a
    channel at the bound for 64 positions ``exp(-Gamma)`` passes float32's
    largest number and the product is not finite, which is why the op forms
    it a block of 16 positions at a time against the block's first row."""
    q, k, v, g, beta = _channel_inputs(1, 128, 1, 1, 16)
    gamma = jnp.cumsum(g[0, 64:128, 0], axis=0)             # (64, d)
    kc = k[0, 64:128]
    two_sided = (kc * jnp.exp(gamma)) @ (kc * jnp.exp(-gamma)).T
    assert not np.isfinite(np.asarray(two_sided)).all()
    kk, qk = ops._channel_products(
        q[0, 64:128][None, None, None], kc[None, None, None],
        gamma[None, None, None], jnp.float32)
    assert np.isfinite(np.asarray(kk)).all() \
        and np.isfinite(np.asarray(qk)).all()
    want = np.tril(np.einsum(
        "id,jd,ijd->ij", *(np.asarray(x, np.float64) for x in (kc, kc)),
        np.exp(np.minimum(np.asarray(gamma, np.float64)[:, None]
                          - np.asarray(gamma, np.float64)[None], 0.0))))
    np.testing.assert_allclose(np.tril(np.asarray(kk[0, 0, 0])), want,
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("Hk,Hv", [(2, 2), (1, 2)])
def test_equal_channels_are_the_rule_under_a_decay_a_head(Hk, Hv):
    """One op: ``g`` (B, S, Hv, dk) with every channel of a head equal is
    ``g`` (B, S, Hv), forward and backward (the channels' cotangents sum to
    the head's)."""
    q, k, v, g, beta = _inputs(2, 128, Hk, Hv, 16, seed=3)
    wide_g = jnp.broadcast_to(g[..., None], g.shape + (16,))
    probe = jnp.asarray(np.random.default_rng(4).standard_normal(
        v.shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        a = _value_and_grads(_chunked(jnp.float32, 64), probe)(
            q, k, v, g, beta)
        b = _value_and_grads(_chunked(jnp.float32, 64), probe)(
            q, k, v, wide_g, beta)
    assert abs(float(a[0]) - float(b[0])) < 2e-5 * (1 + abs(float(a[0])))
    for name, x, y in zip(NAMES, a[1], b[1]):
        assert _rel(y.sum(-1) if name == "g" else y, x) < 2e-5, name


def test_rows_are_independent_under_a_decay_a_channel():
    args = _channel_inputs(2, 128, 2, 2, 16, seed=7)
    both = _chunked(jnp.float32, 64)(*args)
    second = _chunked(jnp.float32, 64)(*(x[1:] for x in args))
    np.testing.assert_allclose(both[1:], second, rtol=1e-5, atol=1e-6)


def test_what_the_rule_refuses_of_a_decay_a_channel():
    q, k, v, g, beta = _channel_inputs(1, 64, 2, 2, 16, pinned=False)
    with pytest.raises(ValueError, match="a decay a key channel is"):
        gated_delta_rule(q, k, v, g[..., :8], beta)
    # float32 heads of 16 channels: what the channel kernels' guard refuses
    with pytest.raises(NotImplementedError,
                       match=r"a decay a key channel \(2 heads x 16\): "
                             r"operands of float32"):
        gated_delta_rule(q, k, v, g, beta, impl="pallas", interpret=True)
    with pytest.raises(ValueError, match="gated_delta_rule takes"):
        gated_delta_rule(q, k, v, g[..., 0, 0], beta)


def test_the_dispatch_says_a_decay_a_channel_and_the_gauge_its_width():
    def channels():
        family = get_registry().snapshot().get("gated_delta_decay_channels")
        return family["samples"][0]["value"] if family else None

    before = {r[:3]: r[3] for r in dispatch_report()}
    jax.eval_shape(lambda *a: gated_delta_rule(*a, chunk=16),
                   *_channel_inputs(1, 64, 2, 2, 16, pinned=False))
    assert channels() == 16
    assert [r[1:3] for r in dispatch_report() if r[0] == "gated_delta"
            and r[3] > before.get(r[:3], 0)] == [(
                "xla", "a decay a key channel (2 heads x 16): operands of "
                "float32")]
    jax.eval_shape(lambda *a: gated_delta_rule(*a, chunk=16),
                   *_inputs(1, 64, 2, 2, 16))
    assert channels() == 1


# ----------------------------------------------------------------------
# the kernels under a decay a key channel (PR 60), in the interpreter
# ----------------------------------------------------------------------
@pytest.mark.parametrize("Hk,Hv,chunk", [(2, 2, 64), (1, 2, 64), (1, 1, 32)])
def test_the_channel_kernels_are_the_xla_form_and_the_recurrence(Hk, Hv,
                                                                 chunk):
    """The kernels of ``g`` (B, S, Hv, dk) at the pinned decays of
    :func:`_channel_inputs` (channels at the bound and at 0 over the whole
    second 64 positions, and across the first chunk's edge), heads of 128
    channels, two rows of 512 positions: two or four grid steps a
    head-sequence, so the resident state and ``dS`` cross a group both
    ways; one and two value heads a key head; chunks of 64 (four solve
    blocks, joined twice) and of 32.  Forward and the five gradients: the
    XLA form's to the rounding of bf16 operands, and as near the per-token
    recurrence as it is; the log-decays' cotangent, formed from float32 sums
    before ``dq`` and ``dk`` are rounded, no further from it."""
    from deepspeed_tpu.ops.pallas import gated_delta as kernel

    assert kernel.channel_supported(512 // chunk, chunk, 128, 128,
                                    jnp.bfloat16, Hv // Hk) is None
    args = _bf16(_channel_inputs(2, 512, Hk, Hv, 128))
    probe = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, 512, Hv * 128)), jnp.float32)
    (want, want_g), (got, got_g) = (_value_and_grads(_fused(chunk, how), probe)(*args)
                                    for how in (None, True))
    assert np.isfinite(float(got))
    assert abs(float(got) - float(want)) < 2e-3 * abs(float(want))
    with jax.default_matmul_precision("highest"):
        exact = _value_and_grads(_channel_recurrence, probe)(
            *(x.astype(jnp.float32) for x in args))[1]
    for name, a, b, c in zip(NAMES, got_g, want_g, exact):
        assert a.shape == c.shape and a.dtype == b.dtype, name
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        assert _rel(a, b) < 8e-3, name
        assert _rel(a, c) < max(6e-3, 1.2 * _rel(b, c)), name


def test_the_channel_kernels_in_float32_are_the_recurrence():
    """The mathematics alone: float32 operands through the same bodies (the
    guard would refuse them; the interpreter does not care) read the
    recurrence to float32's rounding, the log-decays' cotangent closer than
    ``jax.vjp`` of the XLA form, which carries the reference rows' noise."""
    args = _channel_inputs(1, 256, 1, 1, 128, seed=2)
    probe = jnp.asarray(np.random.default_rng(5).standard_normal(
        (1, 256, 128)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = _value_and_grads(_fused(64), probe)(*args)
        by_xla = _value_and_grads(_fused(64, None), probe)(*args)
        want = _value_and_grads(_channel_recurrence, probe)(*args)
    assert abs(float(got[0]) - float(want[0])) < 2e-5 * (
        1 + abs(float(want[0])))
    for name, a, b, c in zip(NAMES, got[1], by_xla[1], want[1]):
        assert _rel(a, c) < 2e-5, name
    assert _rel(got[1][3], want[1][3]) <= _rel(by_xla[1][3], want[1][3])


def test_equal_channels_in_the_kernels_are_the_head_decay_kernels():
    """One op in the kernels too: ``g`` (B, S, Hv, dk) with every channel of
    a head equal gives what the head-decay kernels give of ``g`` (B, S,
    Hv), forward and backward, the channels' cotangents summing to the
    head's (two value heads a key head)."""
    q, k, v, g, beta = _bf16(_inputs(1, 256, 1, 2, 128, seed=3))
    wide_g = jnp.broadcast_to(g[..., None], g.shape + (128,))
    probe = jnp.asarray(np.random.default_rng(4).standard_normal(
        v.shape), jnp.float32)
    a = _value_and_grads(_fused(64), probe)(q, k, v, g, beta)
    b = _value_and_grads(_fused(64), probe)(q, k, v, wide_g, beta)
    assert abs(float(a[0]) - float(b[0])) < 2e-3 * (1 + abs(float(a[0])))
    for name, x, y in zip(NAMES, a[1], b[1]):
        assert _rel(y.sum(-1) if name == "g" else y, x) < 8e-3, name


def test_rows_are_independent_in_the_channel_kernels():
    """A row beside another gives what it gives alone, to the bit, and so
    do its cotangents: no state, gamma row or block index crosses rows or
    key heads."""
    args = _bf16(_channel_inputs(2, 256, 2, 2, 128, seed=7))
    run = _fused(64)
    out = run(*args)
    alone = run(*(x[1:] for x in args))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(alone[0]))
    assert np.abs(np.asarray(out[0] - out[1])).max() > 1e-3
    grads = jax.jit(jax.grad(lambda *a: run(*a).sum(), range(5)))
    for both, one in zip(grads(*args), grads(*(x[1:] for x in args))):
        np.testing.assert_array_equal(np.asarray(both[1], np.float32),
                                      np.asarray(one[0], np.float32))


@pytest.mark.parametrize("n,chunk,dk,dv,dtype,r,said", [
    (8, 32, 128, 128, jnp.float32, 1, "operands of float32"),
    (8, 32, 96, 128, jnp.bfloat16, 1, "heads of 96 and 128 channels"),
    (8, 32, 128, 192, jnp.bfloat16, 1, "heads of 128 and 192 channels"),
    (8, 16, 128, 128, jnp.bfloat16, 1, "chunks of 16 positions"),
    (8, 256, 128, 128, jnp.bfloat16, 1, "chunks of 256 positions"),
    (6, 32, 128, 128, jnp.bfloat16, 1, "6 chunks are no whole groups of 4"),
    (8, 32, 128, 128, jnp.bfloat16, 16, "16 value heads a key head"),
    (8, 64, 512, 512, jnp.bfloat16, 2, "2 states of 512 x 512 float32"),
])
def test_what_the_channel_kernels_refuse_falls_to_xla_and_says_why(
        n, chunk, dk, dv, dtype, r, said):
    from deepspeed_tpu.ops.pallas import gated_delta as kernel

    assert said in kernel.channel_supported(n, chunk, dk, dv, dtype, r)
    S = n * chunk
    args = [jax.ShapeDtypeStruct(shape, t) for shape, t in (
        ((1, S, dk), dtype), ((1, S, dk), dtype), ((1, S, r * dv), dtype),
        ((1, S, r, dk), jnp.float32), ((1, S, r), jnp.float32))]
    with pytest.raises(NotImplementedError, match=said):
        jax.eval_shape(lambda *a: gated_delta_rule(
            *a, chunk=chunk, key_heads=1, impl="pallas"), *args)
    jax.eval_shape(lambda *a: gated_delta_rule(*a, chunk=chunk, key_heads=1),
                   *args)
    assert any(site == "gated_delta" and impl == "xla" and said in why
               and why.startswith(f"a decay a key channel ({r} heads x {dk})")
               for site, impl, why, _ in dispatch_report())


def test_the_plan_names_the_channel_form_and_its_tile():
    """On one device the plan takes the channel kernels and says so in the
    counter's label (what the driver's ``said.gated_delta_impl`` prints);
    the gauge reads the key head's channels."""
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.comm.mesh import build_mesh

    mesh_lib.set_mesh(build_mesh({"dp": 1}, devices=jax.devices()[:1]))
    before = {r[:3]: r[3] for r in dispatch_report()}
    try:
        jax.eval_shape(lambda *a: gated_delta_rule(
            *a, chunk=64, impl="pallas", interpret=True),
            *_bf16(_channel_inputs(1, 256, 2, 2, 128, pinned=False)))
    finally:
        mesh_lib.set_mesh(None)
    assert [r[1:3] for r in dispatch_report() if r[0] == "gated_delta"
            and r[3] > before.get(r[:3], 0)] == [(
                "pallas", "4 chunks of 64 x 2 key heads x 1 value heads of "
                "128, a decay a key channel, fused; one device")]
    family = get_registry().snapshot()["gated_delta_decay_channels"]
    assert family["samples"][0]["value"] == 128
