"""q and k as ``(B, S, H*D)`` rows from projection to flash kernel
(``ops/pallas/qk_rows.py``, ``ops/rotary.py rows_plan / rotate_rows``,
PR 34), the kernels in interpret mode: the rotation against
``apply_rotary`` on the reshaped operands and against a float32 reference,
the per-head norm + rotation against ``rms_norm`` then ``apply_rotary``,
every guard's reason, one trace a signature, the three families' attention
layers with and without the rows path, and what a block's jaxpr holds.
Since PR 53 also a Gated DeltaNet's two per-head norms: the l2-norm as the
same pass under constant scales, the gated output norm's own pass
(``gated_norm_plan`` / ``gated_norm_rows``; in ``ops/gated_delta.py`` with
the slots' plan since PR 56), and the jaxpr digests of the layers of every
family that reaches its kernels through ``ops/pallas/spmd.py``.  Since PR 55 heads that are no
whole lane tiles (Olmo-Hybrid's 96 x 192) in lane slots: ``slot_rows``, the
gated norm reading ``o`` from slots, ``slots_plan``'s guards, the mixer
through the slots against the mixer through the ``(B, S, H, d)`` lines, and
what its jaxpr no longer holds.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.models.common import rms_norm
from deepspeed_tpu.models import llama
from deepspeed_tpu.models.llama import LlamaBlock, LlamaConfig
from deepspeed_tpu.ops import attention, gated_delta, rotary
from deepspeed_tpu.ops.pallas.spmd import dispatch_report
from deepspeed_tpu.telemetry import get_registry

B, S, D = 2, 64, 128
EPS = 1e-5
YARN = dict(rope_type="yarn", rope_theta=500000.0, factor=16.0,
            original_max_position_embeddings=8192)
HEADS = {"mha16": (16, 16), "gqa32_4": (32, 4)}


def _operands(heads, dtype, seed=0):
    H, KV = HEADS[heads]
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = jax.random.normal(ks[0], (B, S, H * D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV * D), jnp.float32).astype(dtype)
    # packed documents: positions restart, and none is its row's index
    pos = jax.random.randint(ks[2], (B, S), 0, 8192)
    scales = 1 + 0.2 * jax.random.normal(ks[3], (2, D), jnp.float32)
    cts = (jax.random.normal(ks[4], q.shape, jnp.float32),
           jax.random.normal(ks[5], k.shape, jnp.float32))
    return q, k, pos, scales, cts


def _today(q, k, pos, table, scales=None, rotate=True):
    """The ``(B, S, H, D)`` path of ``LlamaAttention`` on flat operands."""
    q4, k4 = q.reshape(B, S, -1, D), k.reshape(B, S, -1, D)
    if scales is not None:
        q4, k4 = rms_norm(q4, scales[0], EPS), rms_norm(k4, scales[1], EPS)
    if rotate:
        q4, k4 = rotary.apply_rotary_pos_emb(q4, k4, pos, rotary_dim=D,
                                             table=table)
    return q4.reshape(q.shape), k4.reshape(k.shape)


def _rows(q, k, pos, table, scales=None, rotate=True):
    qs, ks = (None, None) if scales is None else (scales[0], scales[1])
    return rotary.rotate_rows(q, k, pos if rotate else None, D,
                              ("direct", None), table=table, q_scale=qs,
                              k_scale=ks, eps=EPS, interpret=True)


def _weighted(fn, cts):
    def loss(q, k, scales):
        return sum((o.astype(jnp.float32) * g).sum()
                   for o, g in zip(fn(q, k, scales), cts))
    return loss


@functools.lru_cache(maxsize=None)
def _run(heads, table, dtype, norm=False, rotate=True):
    """Values and gradients of the rows path, of today's path, and of
    today's path on float32 operands (the reference)."""
    table = rotary.rotary_table(D, **YARN) if table == "yarn" else None
    dtype = jnp.dtype(dtype)
    q, k, pos, scales, cts = _operands(heads, dtype)
    if not norm:
        scales = None
    argnums = (0, 1, 2) if norm else (0, 1)
    out = {}
    for name, fn, cast in (("rows", _rows, dtype), ("today", _today, dtype),
                           ("f32", _today, jnp.float32)):
        call = lambda q, k, s, fn=fn: fn(q, k, pos, table, s, rotate)
        args = (q.astype(cast), k.astype(cast), scales)
        out[name] = (call(*args), jax.grad(_weighted(call, cts),
                                           argnums=argnums)(*args))
    return out


def _worst(a, b):
    return float(np.abs(np.asarray(a, np.float32)
                        - np.asarray(b, np.float32)).max())


def _rms(a, b):
    return float(np.sqrt(np.mean((np.asarray(a, np.float32)
                                  - np.asarray(b, np.float32)) ** 2)))


def _check(run, part, index, dtype):
    """Within one rounding of the reference's size - two for a gradient,
    whose cotangent arrives rounded to the output's type before it is turned
    and rounded again - and no further from it than today's path (over
    all elements: the worst one is the cotangent's rounding on both)."""
    got, today, want = (run[n][part][index] for n in ("rows", "today", "f32"))
    assert np.isfinite(np.asarray(got, np.float32)).all()
    size = float(np.abs(np.asarray(want)).max())
    one = size * (2.0 ** -8 if dtype == "bfloat16" else 2.0 ** -21)
    assert _worst(got, want) <= one * (1 + part)
    assert _rms(got, want) <= _rms(today, want) + 1e-7 * size


@pytest.mark.parametrize("part", ["values", "grad"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("table", ["default", "yarn"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_rotation_on_rows(heads, table, dtype, part):
    """The flat rotation against ``apply_rotary`` on the reshaped operands:
    MHA 16 x 128 and GQA 32 / 4, both tables (YaRN's factor is in the
    table), positions that are no ``arange``, q and k, forward and
    ``jax.grad``."""
    run = _run(heads, table, dtype)
    for index in (0, 1):
        _check(run, 0 if part == "values" else 1, index, dtype)


@pytest.mark.parametrize("sharded", [False, True], ids=["direct", "shard"])
def test_one_row_of_positions_serves_every_row(sharded):
    """``LlamaForCausalLM`` passes ``arange(S)[None]`` where the caller
    gives no positions: a ``(1, S)`` table, read by every row's blocks (a
    block index past it would read whatever memory holds)."""
    q, k, _, scales, cts = _operands("gqa32_4", jnp.bfloat16)
    pos = jnp.arange(100, 100 + S)[None, :]
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(mesh_lib.build_mesh({"fsdp": 2, "dp": 1},
                                          devices=jax.devices()[:2]))
    try:
        plan = ("shard", ("fsdp",)) if sharded else ("direct", None)
        call = lambda pos: rotary.rotate_rows(q, k, pos, D, plan,
                                              interpret=True)
        for a, b in zip(call(pos), call(jnp.broadcast_to(pos, (B, S)))):
            assert (np.asarray(a, np.float32) == np.asarray(b, np.float32)
                    ).all()
        for a, b in zip(call(pos), _today(q, k, pos, None)):
            assert _worst(a, b) <= 2.0 ** -6 * float(jnp.abs(b).max())
    finally:
        mesh_lib.set_mesh(prev)
    with pytest.raises(ValueError, match="a table of 3 rows for 2"):
        rotary.rotate_rows(q, k, jnp.zeros((3, S), jnp.int32), D,
                           ("direct", None), interpret=True)


def test_yarn_table_carries_its_factor():
    angles = rotary.row_table(jnp.zeros((1, 4), jnp.int32), D,
                              table=rotary.rotary_table(D, **YARN))
    factor = 0.1 * np.log(16.0) + 1.0
    assert angles.shape == (1, 4, D) and angles.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(angles[..., :D // 2]), factor,
                               rtol=1e-6)
    assert not np.asarray(angles[..., D // 2:]).any()


@pytest.mark.parametrize("part", ["values", "dx", "dscale"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("rotate", [True, False], ids=["rotated", "no_rope"])
def test_head_norm_on_rows(rotate, dtype, part):
    """The per-head norm (+ rotation) against ``rms_norm`` over
    ``head_dim`` then ``apply_rotary``: values, dx, and the gradient of
    both 128-wide scales."""
    run = _run("gqa32_4", "default", dtype, norm=True, rotate=rotate)
    if part == "dscale":
        got, today, want = (run[n][1][2] for n in ("rows", "today", "f32"))
        size = float(np.abs(np.asarray(want)).max())
        assert got.shape == (2, D) and got.dtype == jnp.float32
        # sums of B*S*H products of rounded values: both paths carry the
        # operands' rounding, neither more than a part in 100 of the sum
        assert _worst(got, want) <= 1e-2 * size
        assert _worst(got, want) <= _worst(today, want) + 2e-3 * size
        return
    for index in (0, 1):
        _check(run, 0 if part == "values" else 1, index, dtype)


def _booked(site, guard):
    """``(plan, impl, reason)``: what ``guard()`` gave and the one row it
    booked under ``site``."""
    before = {r[:3]: r[3] for r in dispatch_report() if r[0] == site}
    plan = guard()
    new = [r[:3] for r in dispatch_report() if r[0] == site
           and r[3] == before.get(r[:3], 0) + 1]
    assert len(new) == 1
    return plan, new[0][1], new[0][2]


def _plan(monkeypatch, head_dim=128, tpu=True, heads=(4, 2), seq=S,
          dtype=jnp.bfloat16, **kw):
    monkeypatch.setattr(attention, "on_tpu", lambda: tpu)
    q = jax.ShapeDtypeStruct((B, seq, heads[0] * head_dim), dtype)
    k = jax.ShapeDtypeStruct((B, seq, heads[1] * head_dim), dtype)
    return _booked("qk_rows", lambda: rotary.rows_plan(q, k, head_dim, **kw))


@pytest.mark.parametrize("case,kw,reason", [
    ("head_dim_64", dict(head_dim=64), "head_dim 64 is no multiple of 128"),
    ("head_dim_96", dict(head_dim=96), "head_dim 96 is no multiple of 128"),
    ("partial", dict(rotary_dim=64), "rotary_dim 64 < head_dim 128"),
    ("interleaved", dict(interleaved=True), "interleaved pairs"),
    ("decode", dict(decode=True), "decode: the cache keeps (B, S, KV, D)"),
    ("cpu", dict(tpu=False), "no TPU"),
    ("int8", dict(dtype=jnp.int8), "rows of int8"),
    ("float16", dict(dtype=jnp.float16), "rows of float16"),
    ("odd_rows", dict(seq=40), "sequence 40 is no whole number of 32-row "
                               "chunks"),
    ("mesh", dict(), "kernel_mesh_plan refused the mesh"),
])
def test_a_guard_keeps_todays_path_and_says_why(monkeypatch, case, kw, reason):
    if case == "mesh":      # heads over tp: no batch-parallel kernel
        prev = mesh_lib.get_mesh(required=False)
        mesh_lib.set_mesh(mesh_lib.build_mesh({"tp": 2, "dp": -1}))
        try:
            got = _plan(monkeypatch, **kw)
        finally:
            mesh_lib.set_mesh(prev)
    else:
        got = _plan(monkeypatch, **kw)
    assert got == (None, "xla", reason)


@pytest.mark.parametrize("mesh,verdict", [
    (None, ("direct", None)), ({"fsdp": 2, "dp": 1}, ("shard", ("fsdp",)))])
def test_the_plan_engages_on_one_devices_own_rows(monkeypatch, mesh, verdict):
    prev = mesh_lib.get_mesh(required=False)
    devices = jax.devices()[:2 if mesh else 1]
    mesh_lib.set_mesh(mesh_lib.build_mesh(mesh or {"dp": 1}, devices=devices))
    try:
        plan, impl, reason = _plan(monkeypatch)
    finally:
        mesh_lib.set_mesh(prev)
    assert plan == verdict and impl == "pallas"
    assert reason.startswith("head_dim 128, rows 512 + 256; ")


def test_sharded_over_the_batch_it_matches_one_device():
    """Under a mesh with batch axes the pass is a ``shard_map`` over them:
    same values, same gradients, the scales' summed over the ranks."""
    q, k, pos, scales, cts = _operands("gqa32_4", jnp.bfloat16)
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(mesh_lib.build_mesh({"fsdp": 2, "dp": 1},
                                          devices=jax.devices()[:2]))
    try:
        def call(plan):
            fn = lambda q, k, s: rotary.rotate_rows(
                q, k, pos, D, plan, q_scale=s[0], k_scale=s[1], eps=EPS,
                interpret=True)
            return fn(q, k, scales), jax.grad(
                _weighted(fn, cts), argnums=(0, 1, 2))(q, k, scales)

        one, two = call(("direct", None)), call(("shard", ("fsdp",)))
    finally:
        mesh_lib.set_mesh(prev)
    for a, b in zip(jax.tree_util.tree_leaves(one),
                    jax.tree_util.tree_leaves(two)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=2e-5,
                                   atol=1e-5)


# -- a Gated DeltaNet's per-head norms (PR 53) -------------------------------

def _unit(t, d):
    """``GatedDeltaNet``'s l2-norm on the ``(B, S, H, d)`` float32 view."""
    t = t.astype(jnp.float32).reshape(B, S, -1, d)
    return t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)


@functools.lru_cache(maxsize=None)
def _l2_run(d, dtype):
    """As :func:`_run`: q and k to length 1 a head (q also by ``d^-1/2``) by
    the rows pass under constant scales, by the 4-D lines, and by those on
    float32 operands."""
    dtype = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(d), 4)
    q, k = (jax.random.normal(key, (B, S, 4 * d), jnp.float32).astype(dtype)
            for key in ks[:2])
    cts = tuple(jax.random.normal(key, q.shape, jnp.float32)
                for key in ks[2:])

    def today(q, k, _):
        return ((_unit(q, d) * d ** -0.5).astype(q.dtype).reshape(q.shape),
                _unit(k, d).astype(k.dtype).reshape(k.shape))

    def rows(q, k, _):
        return rotary.rotate_rows(
            q, k, None, d, ("direct", None),
            q_scale=jnp.full((d,), 1 / d, jnp.float32),
            k_scale=jnp.full((d,), d ** -0.5, jnp.float32), eps=1e-6 / d,
            interpret=True)

    out = {}
    for name, fn, cast in (("rows", rows, dtype), ("today", today, dtype),
                           ("f32", today, jnp.float32)):
        args = (q.astype(cast), k.astype(cast), None)
        out[name] = (fn(*args), jax.grad(_weighted(fn, cts),
                                         argnums=(0, 1))(*args))
    return out


@pytest.mark.parametrize("part", ["values", "grad"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("d", [128, 256])
def test_constant_scales_make_the_l2_norm(d, dtype, part):
    """``x / |x| = rms_norm(x, d^-1/2, eps / d)``: the norm pass with
    ``q_scale = 1 / d``, ``k_scale = d^-1/2``, ``eps = 1e-6 / d`` and no
    positions IS ``unit(q) d^-1/2`` and ``unit(k)`` of ``GatedDeltaNet``."""
    run = _l2_run(d, dtype)
    for index in (0, 1):
        _check(run, 0 if part == "values" else 1, index, dtype)
        if (dtype, part) == ("float32", "values"):
            assert _worst(run["rows"][0][index], run["today"][0][index]) \
                <= 1e-6


GATED_EPS = 1e-6


def _gated_operands(rows, dtype, d=D, heads=4):
    ks = jax.random.split(jax.random.PRNGKey(rows), 4)
    shape = (rows, S, heads * d)
    o = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    z = (2 * jax.random.normal(ks[1], shape, jnp.float32)).astype(dtype)
    w = 1 + 0.2 * jax.random.normal(ks[2], (d,), jnp.float32)
    return o, z, w, jax.random.normal(ks[3], shape, jnp.float32)


def _gated_today(o, z, w, d=D):
    """``GatedDeltaNet``'s lines on the ``(B, S, H, d)`` float32 view."""
    four = (*o.shape[:2], -1, d)
    y = rms_norm(o.reshape(four).astype(jnp.float32), w, GATED_EPS)
    return (y * jax.nn.silu(z.reshape(four).astype(jnp.float32))).astype(
        o.dtype).reshape(o.shape)


def _gated_rows(o, z, w, d=D, plan=("direct", None)):
    return gated_delta.gated_norm_rows(o, z, w, d, plan, eps=GATED_EPS,
                                  interpret=True)


@functools.lru_cache(maxsize=None)
def _gated_run(rows, dtype, d=D):
    """``(y, do, dz, dw)`` of the kernels, of today's lines, and of those on
    float32 operands."""
    dtype = jnp.dtype(dtype)
    o, z, w, ct = _gated_operands(rows, dtype, d)
    out = {}
    for name, fn, cast in (("rows", _gated_rows, dtype),
                           ("today", _gated_today, dtype),
                           ("f32", _gated_today, jnp.float32)):
        call = lambda o, z, w, fn=fn: fn(o, z, w, d)
        args = (o.astype(cast), z.astype(cast), w)
        out[name] = (call(*args), *jax.grad(
            lambda *a: (call(*a).astype(jnp.float32) * ct).sum(),
            argnums=(0, 1, 2))(*args))
    return out


@pytest.mark.parametrize("part", ["y", "do", "dz", "dw"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("rows,d", [(1, 128), (3, 128), (2, 256)])
def test_gated_norm_on_rows(rows, d, dtype, part):
    """``rms_norm(o, w, eps) * silu(z)`` a head on the rows against the
    lines on the ``(B, S, H, d)`` view: the result and the three gradients,
    a scale that is not ones, one row and three, heads of 128 and 256."""
    index = ("y", "do", "dz", "dw").index(part)
    got, today, want = (_gated_run(rows, dtype, d)[n][index]
                        for n in ("rows", "today", "f32"))
    assert np.isfinite(np.asarray(got, np.float32)).all()
    size = float(np.abs(np.asarray(want)).max())
    if part == "dw":
        assert got.shape == (d,) and got.dtype == jnp.float32
        assert _worst(got, want) <= 1e-2 * size
        assert _worst(got, want) <= _worst(today, want) + 2e-3 * size
        return
    assert got.shape == want.shape and got.dtype == jnp.dtype(dtype)
    # one rounding of the result (two for a gradient, as _check); in
    # float32 the interpreter's approximate reciprocal, which it rounds to
    # bfloat16 before the Newton step: 2^-17 of the sigmoid
    one = size * (2.0 ** -8 if dtype == "bfloat16" else 2.0 ** -15)
    assert _worst(got, want) <= one * (1 + (part != "y"))
    assert _rms(got, want) <= _rms(today, want) + 4e-6 * size


def test_gated_norm_sharded_over_the_batch_matches_one_device():
    o, z, w, ct = _gated_operands(2, jnp.bfloat16)
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(mesh_lib.build_mesh({"fsdp": 2, "dp": 1},
                                          devices=jax.devices()[:2]))
    try:
        def call(plan):
            fn = lambda o, z, w: _gated_rows(o, z, w, plan=plan)
            return fn(o, z, w), jax.grad(
                lambda *a: (fn(*a).astype(jnp.float32) * ct).sum(),
                argnums=(0, 1, 2))(o, z, w)

        one, two = call(("direct", None)), call(("shard", ("fsdp",)))
    finally:
        mesh_lib.set_mesh(prev)
    for a, b in zip(jax.tree_util.tree_leaves(one),
                    jax.tree_util.tree_leaves(two)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=2e-5,
                                   atol=1e-5)


def test_a_gate_or_a_head_of_another_shape_is_refused():
    o, z, w, _ = _gated_operands(2, jnp.bfloat16)
    with pytest.raises(ValueError, match="a gate of"):
        _gated_rows(o, z[:1], w)
    with pytest.raises(ValueError, match="a gate of"):
        _gated_rows(o, z.astype(jnp.float32), w)
    with pytest.raises(ValueError, match="512 lanes are no heads of 384"):
        _gated_rows(o, z, jnp.ones((384,)), d=384)


def _gated_plan(monkeypatch, head_dim=128, tpu=True, heads=4, seq=S,
                dtype=jnp.bfloat16):
    monkeypatch.setattr(attention, "on_tpu", lambda: tpu)
    o = jax.ShapeDtypeStruct((B, seq, heads * head_dim), dtype)
    return _booked("gated_norm_rows",
                   lambda: gated_delta.gated_norm_plan(o, head_dim))


@pytest.mark.parametrize("case,kw,reason", [
    ("head_dim_96", dict(head_dim=96), "head_dim 96 is no multiple of 128"),
    ("head_dim_192", dict(head_dim=192),
     "head_dim 192 is no multiple of 128"),
    ("cpu", dict(tpu=False), "no TPU"),
    ("float16", dict(dtype=jnp.float16), "rows of float16"),
    ("odd_rows", dict(seq=40), "sequence 40 is no whole number of 32-row "
                               "chunks"),
    ("mesh", dict(), "kernel_mesh_plan refused the mesh"),
])
def test_the_gated_norms_guard_keeps_todays_lines_and_says_why(
        monkeypatch, case, kw, reason):
    prev = mesh_lib.get_mesh(required=False)
    if case == "mesh":      # heads over tp: no batch-parallel kernel
        mesh_lib.set_mesh(mesh_lib.build_mesh({"tp": 2, "dp": -1}))
    try:
        got = _gated_plan(monkeypatch, **kw)
    finally:
        mesh_lib.set_mesh(prev)
    assert got == (None, "xla", reason)


@pytest.mark.parametrize("mesh,verdict", [
    (None, ("direct", None)), ({"fsdp": 2, "dp": 1}, ("shard", ("fsdp",)))])
def test_the_gated_norms_plan_engages_on_one_devices_own_rows(
        monkeypatch, mesh, verdict):
    prev = mesh_lib.get_mesh(required=False)
    devices = jax.devices()[:2 if mesh else 1]
    mesh_lib.set_mesh(mesh_lib.build_mesh(mesh or {"dp": 1}, devices=devices))
    try:
        plan, impl, reason = _gated_plan(monkeypatch, head_dim=256)
    finally:
        mesh_lib.set_mesh(prev)
    assert plan == verdict and impl == "pallas"
    assert reason.startswith("head_dim 256, rows 1024; ")


# -- heads that are no whole lane tiles, in lane slots (PR 55) ---------------

# (key heads, dk, value heads, dv): Olmo-Hybrid's widths - k begins half a
# tile in, a head at every lane offset 0 / 32 / 64 / 96 - and a second pair
SLOTTED = {"96x192": (2, 96, 2, 192), "64x160": (2, 64, 4, 160)}


def _slotted(x, heads, d):
    """XLA's pad of ``ops/pallas/gated_delta.py``: a head a slot."""
    from deepspeed_tpu.ops.pallas.gated_delta import _slots

    return _slots(x, heads, d)


def _cut(x, heads, d):
    from deepspeed_tpu.ops.pallas.gated_delta import _unslots

    return _unslots(x, heads, d)


def _slot_lines(x, Hk, dk, Hv, dv):
    """``GatedDeltaNet``'s lines on the ``(B, S, H, d)`` float32 view, then
    XLA's pad into slots."""
    q, k, v = x[..., :Hk * dk], x[..., Hk * dk:2 * Hk * dk], \
        x[..., 2 * Hk * dk:]
    q = (_unit(q, dk) * dk ** -0.5).astype(x.dtype).reshape(q.shape)
    k = _unit(k, dk).astype(x.dtype).reshape(k.shape)
    return _slotted(q, Hk, dk), _slotted(k, Hk, dk), _slotted(v, Hv, dv)


@functools.lru_cache(maxsize=None)
def _slot_run(pair, dtype):
    """``((q, k, v), dx)`` of the slot kernels, of today's lines, and of
    those on float32 operands; the cotangents are random in EVERY lane of
    the slots."""
    from deepspeed_tpu.ops.pallas.qk_rows import slot

    dtype = jnp.dtype(dtype)
    Hk, dk, Hv, dv = SLOTTED[pair]
    ks = jax.random.split(jax.random.PRNGKey(dk), 4)
    x = jax.random.normal(ks[0], (B, S, 2 * Hk * dk + Hv * dv),
                          jnp.float32).astype(dtype)
    cts = tuple(jax.random.normal(key, (B, S, n), jnp.float32) for key, n in
                zip(ks[1:], (Hk * slot(dk), Hk * slot(dk), Hv * slot(dv))))

    def rows(x):
        return gated_delta.slot_rows(x, Hk, dk, Hv, dv, ("direct", None),
                                interpret=True)

    def today(x):
        return _slot_lines(x, Hk, dk, Hv, dv)

    out = {}
    for name, fn, cast in (("rows", rows, dtype), ("today", today, dtype),
                           ("f32", today, jnp.float32)):
        arg = x.astype(cast)
        out[name] = (fn(arg), jax.grad(lambda x: sum(
            (o.astype(jnp.float32) * g).sum()
            for o, g in zip(fn(x), cts)))(arg))
    return out


@pytest.mark.parametrize("part", ["q", "k", "v", "dx"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("pair", sorted(SLOTTED))
def test_the_filters_rows_reach_their_slots_normalised(pair, dtype, part):
    """One row kernel reads ``[q | k | v]`` as the filter wrote them and
    writes ``q / |q| dk^-1/2``, ``k / |k|`` and ``v`` a head a lane slot:
    what the ``(B, S, H, d)`` lines and XLA's pad give, the lanes behind a
    head exactly zero; its backward gives the rows' cotangent and reads no
    lane behind a head (the cotangents here are random there)."""
    from deepspeed_tpu.ops.pallas.qk_rows import slot

    run = _slot_run(pair, dtype)
    Hk, dk, Hv, dv = SLOTTED[pair]
    if part == "dx":
        got, today, want = (run[n][1] for n in ("rows", "today", "f32"))
    else:
        index = "qkv".index(part)
        got, today, want = (run[n][0][index]
                            for n in ("rows", "today", "f32"))
        heads, d = (Hv, dv) if part == "v" else (Hk, dk)
        behind = np.asarray(got, np.float32).reshape(B, S, heads, slot(d))
        assert (behind[..., d:] == 0).all()
    assert got.shape == want.shape and got.dtype == jnp.dtype(dtype)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    if part == "v":
        assert (np.asarray(got) == np.asarray(today)).all()
        return
    size = float(np.abs(np.asarray(want)).max())
    one = size * (2.0 ** -8 if dtype == "bfloat16" else 2.0 ** -21)
    assert _worst(got, want) <= one * (1 + (part == "dx"))
    assert _rms(got, want) <= _rms(today, want) + 1e-7 * size


@functools.lru_cache(maxsize=None)
def _gated_slot_run(pair, dtype):
    """``(y, do, dz, dw)`` of the gated norm that reads ``o`` from slots, of
    today's lines on the cut ``o``, and of those on float32 operands."""
    dtype = jnp.dtype(dtype)
    _, _, H, d = SLOTTED[pair]
    o, z, w, ct = _gated_operands(B, dtype, d, H)

    def rows(o, z, w):
        return gated_delta.gated_norm_rows(_slotted(o, H, d), z, w, d,
                                      ("direct", None), eps=GATED_EPS,
                                      interpret=True)

    out = {}
    for name, fn, cast in (("rows", rows, dtype),
                           ("today", lambda *a: _gated_today(*a, d), dtype),
                           ("f32", lambda *a: _gated_today(*a, d),
                            jnp.float32)):
        args = (o.astype(cast), z.astype(cast), w)
        out[name] = (fn(*args), *jax.grad(
            lambda *a: (fn(*a).astype(jnp.float32) * ct).sum(),
            argnums=(0, 1, 2))(*args))
    return out


@pytest.mark.parametrize("part", ["y", "do", "dz", "dw"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("pair", sorted(SLOTTED))
def test_the_gated_norm_reads_o_from_its_slots(pair, dtype, part):
    """``rms_norm(o, w, eps) * silu(z)`` with ``o`` a head a slot (as the
    delta rule's kernels hand it on) and ``z`` and ``y`` rows of heads side
    by side (as ``in_proj`` wrote and ``out_proj`` reads): the lines on the
    ``(B, S, H, d)`` view, the mean over the head's own channels; ``do``
    (through the pad's transpose: the slots' live lanes), ``dz``, ``dw``."""
    index = ("y", "do", "dz", "dw").index(part)
    got, today, want = (_gated_slot_run(pair, dtype)[n][index]
                        for n in ("rows", "today", "f32"))
    d = SLOTTED[pair][3]
    assert np.isfinite(np.asarray(got, np.float32)).all()
    size = float(np.abs(np.asarray(want)).max())
    if part == "dw":
        assert got.shape == (d,) and got.dtype == jnp.float32
        assert _worst(got, want) <= 1e-2 * size
        assert _worst(got, want) <= _worst(today, want) + 2e-3 * size
        return
    assert got.shape == want.shape and got.dtype == jnp.dtype(dtype)
    one = size * (2.0 ** -8 if dtype == "bfloat16" else 2.0 ** -15)
    assert _worst(got, want) <= one * (1 + (part != "y"))
    assert _rms(got, want) <= _rms(today, want) + 4e-6 * size


def test_the_gated_norms_cotangent_is_zero_behind_a_head():
    """``do`` comes back a head a slot, exactly zero behind it."""
    from deepspeed_tpu.ops.pallas import qk_rows

    H, d = 2, 192
    o, z, w, ct = _gated_operands(1, jnp.bfloat16, d, H)
    do, dz, dw = qk_rows.gated_norm_call(
        _slotted(o, H, d), z, w, ct.astype(o.dtype), head_dim=d,
        eps=GATED_EPS, interpret=True)
    assert do.shape == (1, S, H * 256) and dz.shape == z.shape
    assert (np.asarray(do, np.float32).reshape(1, S, H, 256)[..., d:]
            == 0).all()
    assert dw.shape == (d,)


def _slots_plan(monkeypatch, pair="96x192", tpu=True, seq=256,
                dtype=jnp.bfloat16, chunk=64, heads=None):
    monkeypatch.setattr(attention, "on_tpu", lambda: tpu)
    Hk, dk, Hv, dv = heads or SLOTTED[pair]
    rows = jax.ShapeDtypeStruct((B, seq, 2 * Hk * dk + Hv * dv), dtype)
    guard = lambda: gated_delta.slots_plan(rows, Hk, dk, Hv, dv, chunk)
    # one decision, booked under both sites
    norm = {}
    plan, impl, reason = _booked("qk_rows", lambda: norm.update(
        zip(("plan", "impl", "reason"), _booked("gated_norm_rows", guard)))
        or norm["plan"])
    assert (norm["plan"], norm["impl"]) == (plan, impl)
    return plan, impl, reason, norm["reason"]


@pytest.mark.parametrize("case,kw,reason", [
    ("cpu", dict(tpu=False), "no TPU"),
    ("float32", dict(dtype=jnp.float32),
     "the delta rule keeps XLA's form: operands of float32"),
    ("chunk", dict(chunk=16), "the delta rule keeps XLA's form: chunks of 16 "
     "positions: the kernels take 32, 64, 128 (a group of 4 fills whole "
     "tiles of 128 lanes, 16-row blocks join in pairs)"),
    ("half_a_period", dict(heads=(1, 96, 2, 192)),
     "[q | k] of 192 lanes and v of 384 are no whole lane tiles"),
    ("mesh", dict(), "kernel_mesh_plan refused the mesh"),
])
def test_the_slots_guard_keeps_todays_lines_and_says_why(monkeypatch, case,
                                                         kw, reason):
    prev = mesh_lib.get_mesh(required=False)
    if case == "mesh":      # heads over tp: no batch-parallel kernel
        mesh_lib.set_mesh(mesh_lib.build_mesh({"tp": 2, "dp": -1}))
    try:
        got = _slots_plan(monkeypatch, **kw)
    finally:
        mesh_lib.set_mesh(prev)
    assert got == (None, "xla", reason, reason)


@pytest.mark.parametrize("mesh,verdict", [
    (None, ("direct", None)), ({"fsdp": 2, "dp": 1}, ("shard", ("fsdp",)))])
def test_the_slots_plan_engages_on_one_devices_own_rows(monkeypatch, mesh,
                                                        verdict):
    prev = mesh_lib.get_mesh(required=False)
    devices = jax.devices()[:2 if mesh else 1]
    mesh_lib.set_mesh(mesh_lib.build_mesh(mesh or {"dp": 1}, devices=devices))
    try:
        plan, impl, rows, norm = _slots_plan(monkeypatch)
    finally:
        mesh_lib.set_mesh(prev)
    assert plan == verdict and impl == "pallas"
    assert rows.startswith(
        "heads of 96 and 192 in slots of 128 and 256, rows 768; ")
    assert norm.startswith("head_dim 192 in slots of 256, rows 384; ")


def test_slots_sharded_over_the_batch_match_one_device():
    Hk, dk, Hv, dv = SLOTTED["96x192"]
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, 768),
                          jnp.float32).astype(jnp.bfloat16)
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(mesh_lib.build_mesh({"fsdp": 2, "dp": 1},
                                          devices=jax.devices()[:2]))
    try:
        def call(plan):
            fn = lambda x: gated_delta.slot_rows(x, Hk, dk, Hv, dv, plan,
                                            interpret=True)
            return fn(x), jax.grad(lambda x: sum(
                (o.astype(jnp.float32) ** 2).sum() for o in fn(x)))(x)

        one, two = call(("direct", None)), call(("shard", ("fsdp",)))
    finally:
        mesh_lib.set_mesh(prev)
    for a, b in zip(jax.tree_util.tree_leaves(one),
                    jax.tree_util.tree_leaves(two)):
        assert (np.asarray(a) == np.asarray(b)).all()


def _olmo_mixer(seq=256):
    """A Gated DeltaNet layer at Olmo-Hybrid's head widths, cut in count."""
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=256, intermediate_size=256,
        num_hidden_layers=1, num_attention_heads=2, head_dim=128,
        max_position_embeddings=256, linear_num_key_heads=2,
        linear_num_value_heads=2, linear_key_head_dim=96,
        linear_value_head_dim=192, linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True, scan_layers=False)
    return cfg, llama.GatedDeltaNet(cfg), jax.ShapeDtypeStruct(
        (B, seq, cfg.hidden_size), cfg.dtype)


MIXER_PARTS = ("output", "A_log", "conv_kernel", "dt_bias", "in_proj_ba",
               "in_proj_qkvz_kernel", "o_norm", "out_proj", "input")


@functools.lru_cache(maxsize=None)
def _olmo_mixer_run():
    """``MIXER_PARTS`` of the mixer through today's lines (the rule's
    kernels behind XLA's pads and cuts) and through the slots (the plan
    forced, every kernel in the interpreter)."""
    cfg, mixer, shape = _olmo_mixer()
    h = jax.random.normal(jax.random.PRNGKey(5), shape.shape,
                          jnp.float32).astype(cfg.dtype)
    p = meta.unbox(jax.jit(mixer.init)(jax.random.PRNGKey(0), h)["params"])
    p = dict(p, o_norm=p["o_norm"] * (1 + 0.1 * jnp.cos(jnp.arange(192.0))))
    ct = jax.random.normal(jax.random.PRNGKey(6), h.shape)

    def measure():
        return jax.tree_util.tree_leaves(jax.jit(lambda p, h: (
            mixer.apply({"params": p}, h), jax.grad(
                lambda p, h: (mixer.apply({"params": p}, h).astype(
                    jnp.float32) * ct).sum(), argnums=(0, 1))(p, h)))(p, h))

    mp = pytest.MonkeyPatch()
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(mesh_lib.build_mesh({"dp": 1},
                                          devices=jax.devices()[:1]))
    try:
        mp.setattr(gated_delta, "heads_rule", functools.partial(
            gated_delta.heads_rule, impl="pallas", interpret=True))
        today = measure()
        mp.setattr(gated_delta, "slots_plan",
                   lambda *a, **kw: ("direct", None))
        for part in ("normalised_heads", "gated_norm"):
            mp.setattr(gated_delta, part, functools.partial(
                getattr(gated_delta, part), interpret=True))
        slots = measure()
    finally:
        mp.undo()
        mesh_lib.set_mesh(prev)
    assert len(today) == len(slots) == len(MIXER_PARTS)
    return dict(zip(MIXER_PARTS, zip(today, slots)))


@pytest.mark.parametrize("part", MIXER_PARTS)
def test_the_mixer_through_the_slots_is_the_mixer(part):
    """Olmo-Hybrid's mixer (heads of 96 x 192, beta in (0, 2)) with q, k, v
    written into slots by ``slot_rows``, the rule on slotted operands and
    the gated norm reading slotted ``o``, against today's lines around the
    same rule's kernels: the output and every gradient, within the limit
    the attention families' blocks are held to."""
    today, slots = _olmo_mixer_run()[part]
    assert today.shape == slots.shape and today.dtype == slots.dtype
    a, b = np.asarray(today, np.float32), np.asarray(slots, np.float32)
    assert np.isfinite(b).all()
    assert np.abs(a - b).max() <= 2e-2 * max(np.abs(a).max(), 1e-6)


def test_no_operation_of_the_slotted_mixer_sees_b_s_h_d(monkeypatch):
    """Forward + backward of one remat Olmo-Hybrid mixer as the chip traces
    it: under ``linear_attn/delta_rule`` and ``/gated_norm`` no ``pad``, no
    4-D ``reshape`` of q, k, v, o and no ``(B, S, H, d)`` operand at all -
    the slot kernels, the rule's two and the gated norm's pair read and
    write ``(B, S, lanes)``; what is 4-D there is the gates' ``(B, Hk, 8,
    S)`` and the saved states."""
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cfg, mixer, x = _olmo_mixer()
    s = x.shape[1]
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(mesh_lib.build_mesh({"dp": 1},
                                          devices=jax.devices()[:1]))
    try:
        params = meta.unbox(jax.eval_shape(
            mixer.init, jax.random.PRNGKey(0), x)["params"])

        @jax.checkpoint
        def layer(p, x):
            return mixer.apply({"params": p}, x)

        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p, x: (layer(p, x).astype(jnp.float32) ** 2).mean(),
            argnums=(0, 1)))(params, x)
        engaged = {r[:2] for r in dispatch_report() if r[3]
                   and "slots of" in r[2]}
    finally:
        mesh_lib.set_mesh(prev)
    seen, found = {}, set()

    def walk(jaxpr, inside):        # a called jaxpr's stacks are relative
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            scope = str(eqn.source_info.name_stack)
            within = inside or "linear_attn/delta_rule" in scope \
                or "linear_attn/gated_norm" in scope
            if name == "pallas_call":
                seen[eqn.params["name"]] = seen.get(eqn.params["name"], 0) + 1
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, within)
            if not within:
                continue
            for var in (*eqn.invars, *eqn.outvars):
                shape = getattr(var.aval, "shape", ())
                # q, k, v, o and their cotangents, whole or a row of the
                # batch: (.., S, lanes) padded, or a head an axis
                if name == "pad" and len(shape) >= 2 and shape[-2] == s \
                        and shape[-1] >= 96:
                    found.add((name, shape))
                if len(shape) >= 3 and shape[-3] == s and shape[-2] == 2 \
                        and shape[-1] in (96, 128, 192, 256):
                    found.add((name, shape))

    walk(jaxpr.jaxpr, False)
    assert not found, found
    assert engaged == {("qk_rows", "pallas"), ("gated_norm_rows", "pallas")}
    # the forward, the remat's forward, the backward; the rule's forward
    # again for the states
    assert seen == {"causal_conv_rows": 2, "causal_conv_rows_back": 1,
                    "slot_rows": 2, "slot_rows_back": 1,
                    "gated_delta_fwd": 2 * B + B, "gated_delta_bwd": B,
                    "gated_norm_rows": 2, "gated_norm_rows_back": 1} or \
        seen == {"causal_conv_rows": 2, "causal_conv_rows_back": 1,
                 "slot_rows": 2, "slot_rows_back": 1,
                 "gated_delta_fwd": 3, "gated_delta_bwd": 1,
                 "gated_norm_rows": 2, "gated_norm_rows_back": 1}, seen


# -- the three families' attention layers -----------------------------------

def _family(name, head_dim=D):
    """A block at one cell's attention shape, cut in width."""
    base = dict(vocab_size=256, hidden_size=256, intermediate_size=256,
                num_hidden_layers=1, max_position_embeddings=256,
                head_dim=head_dim, remat=True, scan_layers=False)
    if name == "olmoe":         # MHA, whole-projection norm, one table
        return LlamaConfig(num_attention_heads=4, qk_norm=True, **base)
    grouped = dict(num_attention_heads=8, num_key_value_heads=2,
                   layer_types=("sliding_attention", "full_attention"),
                   sliding_window=16, **base)
    if name == "mellum2":       # GQA, a table a layer type, YaRN on full
        return LlamaConfig(rope_parameters={
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000.0},
            "full_attention": YARN}, **grouped)
    return LlamaConfig(qk_norm="head", attn_gate=True,      # trinity
                       rope_layer_types=("sliding_attention",), **grouped)


def _interpreted(monkeypatch):
    """The path the chip takes, on this CPU: the guards see a TPU, the
    row kernels run in the interpreter, attention stays XLA's."""
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(llama, "rotate_rows", functools.partial(
        rotary.rotate_rows, interpret=True))


FAMILIES = [("olmoe", None), ("mellum2", "sliding_attention"),
            ("mellum2", "full_attention"), ("trinity", "sliding_attention"),
            ("trinity", "full_attention")]


@functools.lru_cache(maxsize=None)
def _block_run(name, kind):
    cfg = _family(name)
    cfg = LlamaConfig(**{**cfg.__dict__, "attn_impl": "jnp"})
    block = LlamaBlock(cfg, kind=kind)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.hidden_size),
                          jnp.float32).astype(cfg.dtype)
    pos = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, 256)
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(mesh_lib.build_mesh({"dp": 1},
                                          devices=jax.devices()[:1]))
    mp = pytest.MonkeyPatch()
    try:
        params = meta.unbox(jax.jit(block.init)(jax.random.PRNGKey(0), x,
                                                (pos, None))["params"])
        # scales away from one, or their gradient is all the check sees
        params = jax.tree_util.tree_map_with_path(
            lambda p, v: v * (1 + 0.1 * jnp.cos(jnp.arange(v.size))).reshape(
                v.shape) if "norm" in str(p) else v, params)

        def loss(p, x):
            y = block.apply({"params": p}, x, (pos, None))[0]
            return (y.astype(jnp.float32) ** 2).mean()

        def measure():
            out = block.apply({"params": params}, x, (pos, None))[0]
            return out, jax.grad(loss, argnums=(0, 1))(params, x)

        today = measure()
        _interpreted(mp)
        before = [r for r in dispatch_report()
                  if r[:2] == ("qk_rows", "pallas")]
        rows = measure()
        engaged = [r for r in dispatch_report()
                   if r[:2] == ("qk_rows", "pallas")]
    finally:
        mp.undo()
        mesh_lib.set_mesh(prev)
    return today, rows, sum(r[3] for r in engaged) \
        - sum(r[3] for r in before)


@pytest.mark.parametrize("name,kind", FAMILIES)
def test_a_familys_block_gives_the_same_with_the_rows_path(name, kind):
    """OLMoE's, Mellum 2's and Trinity's attention shapes at head_dim 128,
    a block forward and ``jax.grad``: the rows path engages (also for
    Trinity's full layer, which only normalises) and gives what the
    ``(B, S, H, D)`` path gives, to the rounding the two differ by."""
    today, rows, engaged = _block_run(name, kind)
    assert engaged >= 2         # forward, and again under grad of the remat
    for a, b in zip(jax.tree_util.tree_leaves(today),
                    jax.tree_util.tree_leaves(rows)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(b).all()
        assert np.abs(a - b).max() <= 2e-2 * max(np.abs(a).max(), 1e-6)


def test_decode_and_small_heads_keep_the_4d_path(monkeypatch):
    """``decode=True`` (the cache keeps ``(B, S, KV, D)``) and head_dim 64
    fall back inside the model, each under its guard's name."""
    _interpreted(monkeypatch)
    for cfg, kind, reason in (
            (LlamaConfig(**{**_family("olmoe").__dict__, "decode": True,
                            "remat": False}), None,
             "decode: the cache keeps (B, S, KV, D)"),
            (_family("mellum2", head_dim=64), "full_attention",
             "head_dim 64 is no multiple of 128")):
        before = {r[:3]: r[3] for r in dispatch_report()}
        block = LlamaBlock(cfg, kind=kind)
        x = jnp.zeros((B, 16, cfg.hidden_size), cfg.dtype)
        pos = jnp.zeros((B, 16), jnp.int32)
        jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), x,
                                          (pos, None)))
        key = ("qk_rows", "xla", reason)
        after = {r[:3]: r[3] for r in dispatch_report()}
        assert after.get(key, 0) > before.get(key, 0)
        assert not [k for k in after if k[:2] == ("qk_rows", "pallas")
                    and after[k] != before.get(k, 0)]


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations call,
    kernel bodies apart."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("name,kind", FAMILIES)
def test_no_rotary_or_norm_operation_sees_b_s_h_d(monkeypatch, name, kind):
    """Forward + backward of one remat block as the chip traces it (the
    guards see a TPU: flash and the row kernels are ``pallas_call``
    equations): the only operations with a ``(B, S, heads, head_dim)``
    operand or result are the reshapes either side of the attention call,
    which fold away between two ``(B, S, H*D)`` kernels - no multiply, no
    slice, no concatenate, no reduction has one."""
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cfg = _family(name)
    block = LlamaBlock(cfg, kind=kind)
    s = 128                     # the flash kernel's least
    x = jax.ShapeDtypeStruct((B, s, cfg.hidden_size), cfg.dtype)
    pos = jax.ShapeDtypeStruct((B, s), jnp.int32)
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(mesh_lib.build_mesh({"dp": 1},
                                          devices=jax.devices()[:1]))
    try:
        params = meta.unbox(jax.eval_shape(
            block.init, jax.random.PRNGKey(0), x, (pos, None))["params"])

        @jax.checkpoint         # as the stack wraps each block
        def layer(p, x, pos):
            return block.apply({"params": p}, x, (pos, None))[0]

        def loss(p, x, pos):
            return (layer(p, x, pos).astype(jnp.float32) ** 2).mean()

        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x, pos)
    finally:
        mesh_lib.set_mesh(prev)
    heads = {cfg.num_attention_heads, cfg.kv_heads}
    seen = {"qk_rows": 0, "qk_rows_back": 0}
    four_d = set()
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            if eqn.params["name"] in seen:
                seen[eqn.params["name"]] += 1
        for var in (*eqn.invars, *eqn.outvars):
            shape = getattr(var.aval, "shape", ())
            if len(shape) == 4 and shape[:2] == (B, s) \
                    and shape[2] in heads and shape[3] == D:
                four_d.add(eqn.primitive.name)
    assert four_d <= {"reshape"}, four_d
    # the forward, the remat's forward, the backward
    assert seen == {"qk_rows": 2, "qk_rows_back": 1}


def test_each_kernel_body_is_traced_once_a_signature():
    """Two layers of one shape, forward and gradient twice over: the
    builder behind ``jax.jit`` is entered once a signature and
    tracing context, not once a call."""
    q, k, pos, scales, cts = _operands("gqa32_4", jnp.bfloat16, seed=5)
    q, k = q[:, :32], k[:, :32]         # a signature of this test's own
    pos, cts = pos[:, :32], tuple(c[:, :32] for c in cts)

    def two_layers(q, k, s):
        for _ in range(2):
            q, k = rotary.rotate_rows(q, k, pos, D, ("direct", None),
                                      q_scale=s[0], k_scale=s[1], eps=EPS,
                                      interpret=True)
        return q, k

    def traces():
        family = get_registry().snapshot().get("qk_rows_traces_total")
        return {(s["labels"]["kernel"], s["labels"]["signature"]): s["value"]
                for s in (family["samples"] if family else ())
                if str(q.shape) in s["labels"]["signature"]}

    for _ in range(2):
        jax.eval_shape(two_layers, q, k, scales)
        jax.eval_shape(jax.grad(_weighted(two_layers, cts),
                                argnums=(0, 1, 2)), q, k, scales)
    got = traces()
    assert {kernel for kernel, _ in got} == {"fwd", "back"}
    # the plain context and the one under grad
    assert all(n <= 2 for n in got.values()), got


def _who_else(case):
    """``traced_digest`` of forward and gradient of one remat layer of a
    family that runs the dispatch code and must not feel a change of it,
    as the chip traces it (the guards see a TPU; nothing is lowered)."""
    from deepspeed_tpu.models.llama import GatedDeltaNet, ShortConv
    from deepspeed_tpu.parallel.moe import MoEConfig, MoELayer
    from tests.unit.flash_parent_sweep import traced_digest

    mixer = dict(
        vocab_size=256, hidden_size=256, intermediate_size=256,
        num_hidden_layers=1, num_attention_heads=2, head_dim=128,
        max_position_embeddings=256, linear_num_key_heads=2,
        linear_conv_kernel_dim=4, scan_layers=False)
    extra = ()
    if case == "qwen3next_mixer":   # states of 128 x 128: a slot is the head
        cfg = LlamaConfig(linear_num_value_heads=4, linear_key_head_dim=128,
                          linear_value_head_dim=128, **mixer)
        layer, rows = GatedDeltaNet(cfg), 256
    elif case in ("olmo_mixer_slots", "olmo_mixer_view"):
        # states of 96 x 192 in lane slots; over two chunks, which the
        # rule's kernels refuse, on the (B, S, H, d) lines
        cfg, layer, _ = _olmo_mixer()
        rows = 256 if case == "olmo_mixer_slots" else 128
    elif case == "lfm2_conv":       # the gated filter's kernels
        cfg = LlamaConfig(conv_L_cache=3, **{**mixer, "hidden_size": 512})
        layer, rows = ShortConv(cfg), 256
    elif case == "moe_share":       # the row kernels and the grouped matmul
        cfg = LlamaConfig(**mixer)
        layer = MoELayer(MoEConfig(
            num_experts=8, top_k=8, drop_tokens=False, norm_topk_prob=True,
            expert_act="swiglu", routed_experts=32, first_expert=8),
            model_dim=2048, hidden_dim=128, dtype=jnp.bfloat16)
    else:                               # rotate_rows as its callers call it
        cfg = _family(case[0])
        layer, rows = LlamaBlock(cfg, kind=case[1]), 128
        extra = ((jax.ShapeDtypeStruct((B, 128), jnp.int32), None),)
    if case == "moe_share":
        args = (jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16),)
    else:
        args = (jax.ShapeDtypeStruct((B, rows, cfg.hidden_size), cfg.dtype),
                *extra)
    params = meta.unbox(jax.eval_shape(
        layer.init, jax.random.PRNGKey(0), *args)["params"])

    @jax.checkpoint
    def run(p, *a):
        out = layer.apply({"params": p}, *a)
        return out[0] if isinstance(out, tuple) else out

    def loss(p, *a):
        return (run(p, *a).astype(jnp.float32) ** 2).mean()

    return traced_digest(jax.grad(loss, argnums=(0, 1)), params, *args)


# case, whether the rows of the batch lie over two devices (fsdp), digest
PARENT_JAXPRS = [
    ("qwen3next_mixer", False,
     "f3648aba7f5c371f95d3f0f4bd97b33bd0a8f38b2c14b610edbdaeddd680a2ec"),
    (("mellum2", "sliding_attention"), False,
     "f9a3797369c672382678c9734c1f72e2be774d5c1c0da0df97908748d10dedba"),
    (("trinity", "full_attention"), False,
     "7a2d63ab09f0b7273aad3f58e63ed2f2b9b99d90a8e4bcb17cb18f467f090828"),
    ("olmo_mixer_slots", False,
     "775640de3043693b42d1aa51a7519ac3100e3079cc632858ae75d677ce45c30f"),
    ("olmo_mixer_view", False,
     "10261ba4ace854d7f005b4eb8621ff1d66406e5c0cbca56bf468323caf4687ac"),
    ("lfm2_conv", False,
     "c807d7bd6376176ef17c18903ffed0bb75347b7e4af25b8e66b4932479939e2d"),
    # PR 61's tree: it changes a share's expert layer by design (one
    # product with [gate | up], the SwiGLU row kernels, the grouped
    # matmul's backward ordered); a7051f79... at its parent, 5f07282
    ("moe_share", False,
     "4c97ec5a0789a9a7f5026c0af271fd7b771d42c87e7cd4b479a18cba7e392559"),
    ("qwen3next_mixer", True,
     "1381897984f2b07a7377c24970bd8627084c98d55e3efba7066ebae0824c8e9c"),
    ("olmo_mixer_slots", True,
     "ffa9ae9af6d6e4da3e8d058a941695f8e57af7ed6e0a2798d77b902df3d3c9ee"),
    ("lfm2_conv", True,
     "5810e6575d9a67f66c99753b1f512dd35665e12031ea8651c23173596091d4a5"),
    (("mellum2", "sliding_attention"), True,
     "97785682b74a4a2242d3081ad84515c5336c45c86df90d8e0d8fdbee42d85c0f"),
]


@pytest.mark.parametrize("case,sharded,digest", PARENT_JAXPRS, ids=[
    (case if isinstance(case, str) else "_".join(case).replace(
        "_attention", "")) + ("_fsdp" if sharded else "")
    for case, sharded, _ in PARENT_JAXPRS])
def test_who_else_runs_the_code_traces_to_the_parents_jaxpr(monkeypatch,
                                                            case, sharded,
                                                            digest):
    """The layers that reach a Pallas kernel through the dispatch code
    trace, forward and backward, to what the parent of the PR that last
    moved that code traces.  PR 53 (parent ``d2fe5c3``) put
    ``GatedDeltaNet``'s norms behind plans: the first three.  PR 56 (parent
    ``0e0aa8f``) put every family behind ``ops/pallas/spmd.py plan`` /
    ``over_batch`` and the mixer's layouts behind ``ops/gated_delta.py``:
    the mixer on rows, in slots and on the ``(B, S, H, d)`` view, LFM2's
    gated filter, a share's expert layer, and four of them again with the
    batch over two devices, where the ``shard_map`` and its specs are in
    the digest.  All were computed there with :func:`_who_else`, but the
    share's expert layer, which PR 61 changed and re-pinned on its tree."""
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(mesh_lib.build_mesh(
        {"fsdp": 2, "dp": 1} if sharded else {"dp": 1},
        devices=jax.devices()[:2 if sharded else 1]))
    try:
        assert _who_else(case) == digest
    finally:
        mesh_lib.set_mesh(prev)


PARENT_GPT2_STABLEHLO = \
    "d0a05bd95461cb3cbc57852427522feb1fc89ee01547c8f3e8e4bbfb754c4628"


def test_gpt2_lowers_to_the_parents_stablehlo():
    """``models/gpt2.py`` has no rotary and is not edited: loss and gradient
    of two remat blocks lower to what they lowered to before PR 34."""
    import hashlib

    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    model = GPT2LMHeadModel(gpt2_config("gpt2-tiny", n_layer=2,
                                        scan_layers=False, remat=True))
    ids = jnp.zeros((2, 48), jnp.int32)
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(None)
    try:
        shapes = meta.unbox(jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                           ids))
        text = jax.jit(jax.value_and_grad(
            lambda p, ids: model.apply(p, ids, labels=ids)["loss"])).lower(
                shapes, ids).as_text()
    finally:
        mesh_lib.set_mesh(prev)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_GPT2_STABLEHLO
