"""q and k as ``(B, S, H*D)`` rows from projection to flash kernel
(``ops/pallas/qk_rows.py``, ``ops/rotary.py rows_plan / rotate_rows``,
PR 34), the kernels in interpret mode: the rotation against
``apply_rotary`` on the reshaped operands and against a float32 reference,
the per-head norm + rotation against ``rms_norm`` then ``apply_rotary``,
every guard's reason, one trace a signature, the three families' attention
layers with and without the rows path, and what a block's jaxpr holds.
Since PR 53 also a Gated DeltaNet's two per-head norms: the l2-norm as the
same pass under constant scales, the gated output norm's own pass
(``gated_norm_plan`` / ``gated_norm_rows``), and the jaxpr digests of the
layers of other families that run this code.
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
from deepspeed_tpu.ops import attention, rotary
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
    return rotary.gated_norm_rows(o, z, w, d, plan, eps=GATED_EPS,
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
                   lambda: rotary.gated_norm_plan(o, head_dim))


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
        params = meta.unbox(block.init(jax.random.PRNGKey(0), x,
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
    family that runs the code PR 53 touched and must not feel it, as the
    chip traces it (the guards see a TPU; nothing is lowered)."""
    from deepspeed_tpu.models.llama import GatedDeltaNet
    from tests.unit.flash_parent_sweep import traced_digest

    if case == "olmo_hybrid_mixer":     # states of 96 x 192: both norms stay
        cfg = LlamaConfig(
            vocab_size=256, hidden_size=256, intermediate_size=256,
            num_hidden_layers=1, num_attention_heads=2, head_dim=128,
            max_position_embeddings=256, linear_num_key_heads=2,
            linear_num_value_heads=2, linear_key_head_dim=96,
            linear_value_head_dim=192, linear_conv_kernel_dim=4,
            linear_allow_neg_eigval=True, scan_layers=False)
        layer = GatedDeltaNet(cfg)
        args = (jax.ShapeDtypeStruct((B, 256, cfg.hidden_size), cfg.dtype),)
    else:                               # rotate_rows as its callers call it
        cfg = _family(case[0])
        layer = LlamaBlock(cfg, kind=case[1])
        args = (jax.ShapeDtypeStruct((B, 128, cfg.hidden_size), cfg.dtype),
                (jax.ShapeDtypeStruct((B, 128), jnp.int32), None))
    params = meta.unbox(jax.eval_shape(
        layer.init, jax.random.PRNGKey(0), *args)["params"])

    @jax.checkpoint
    def run(p, *a):
        out = layer.apply({"params": p}, *a)
        return out[0] if isinstance(out, tuple) else out

    def loss(p, *a):
        return (run(p, *a).astype(jnp.float32) ** 2).mean()

    return traced_digest(jax.grad(loss, argnums=(0, 1)), params, *args)


@pytest.mark.parametrize("case,digest", [
    ("olmo_hybrid_mixer",
     "50aa14d5485701588263394160410285f36931ed1f35747b3bf6cff1a64e23cf"),
    (("mellum2", "sliding_attention"),
     "f9a3797369c672382678c9734c1f72e2be774d5c1c0da0df97908748d10dedba"),
    (("trinity", "full_attention"),
     "7a2d63ab09f0b7273aad3f58e63ed2f2b9b99d90a8e4bcb17cb18f467f090828"),
], ids=["olmo_hybrid_mixer", "mellum2_sliding", "trinity_full"])
def test_who_else_runs_the_code_traces_to_the_parents_jaxpr(monkeypatch,
                                                            case, digest):
    """PR 53 put ``GatedDeltaNet``'s norms behind ``rows_plan`` /
    ``gated_norm_plan`` and ``rotate_rows``' ``shard_map`` behind a helper.
    The Olmo-Hybrid mixer (heads of 96 and 192 channels: both guards
    refuse), Mellum 2's rotation and Trinity's norm without rotation still
    trace, forward and backward, to what commit ``d2fe5c3`` (the parent)
    traces: the digests were computed there with :func:`_who_else`."""
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    prev = mesh_lib.get_mesh(required=False)
    mesh_lib.set_mesh(mesh_lib.build_mesh({"dp": 1},
                                          devices=jax.devices()[:1]))
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
