"""The hyper-connections' row kernels (PR 65, ``ops/pallas/mhc_rows.py``
behind ``ops/hyper_connection.py read`` / ``write``) in the interpreter
against the ``jax.numpy`` form, through ``models/llama.py HyperConnection``
and its ``post``: what a sublayer reads, the three maps, the stream after,
and the gradient of a seeded scalar in everything differentiable; what a
refused shape runs and books; that two sublayers trace a body once."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from deepspeed_tpu.models.llama import HyperConnection, LlamaConfig
from deepspeed_tpu.ops import hyper_connection as mhc
from deepspeed_tpu.ops.pallas import mhc_rows
from deepspeed_tpu.ops.pallas.spmd import Plan, dispatch_report
from deepspeed_tpu.telemetry.registry import get_registry

# lanes, channels a lane, rows of the batch, tokens a row, the stream's dtype
CASES = {"4x128-f32": (4, 128, 2, 64, jnp.float32),
         "4x256-bf16": (4, 256, 1, 128, jnp.bfloat16),
         "2x128-bf16": (2, 128, 2, 256, jnp.bfloat16)}
VALUES = ("u", "H_pre", "H_post", "H_res", "X'")
GRADS = ("dX", "dy", "dphi", "da_pre", "da_post", "da_res", "db_pre",
         "db_post", "db_res")
GAINS = ("da_pre", "da_post", "da_res")


def _config(n, E):
    return LlamaConfig(hidden_size=E, num_attention_heads=2, hc_mult=n,
                       scan_layers=False, rms_norm_eps=1e-6)


def _leaves(module, n, E, seed=0):
    """A sublayer's leaves away from their near-identity start, where every
    term of the backward is alive."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = meta.unbox(module.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 16, n * E), jnp.float32)))
    p = dict(p["params"])
    p["phi"] = 0.05 * jax.random.normal(ks[0], p["phi"].shape, jnp.float32)
    for name, a in zip(("a_pre", "a_post", "a_res"), (0.7, 0.5, 0.9)):
        p[name] = jnp.full((1,), a, jnp.float32)
    p["b_pre"], p["b_post"] = (jax.random.normal(k, (n,)) for k in ks[1:3])
    p["b_res"] = jax.random.normal(ks[3], (n, n)) + 3.0 * jnp.eye(n)
    return p


def _with_kernels(monkeypatch):
    """The guard answers as on one chip; the calls run in the interpreter."""
    monkeypatch.setattr(mhc, "_plan", lambda x, n: Plan("direct", None))
    monkeypatch.setattr(mhc, "read",
                        functools.partial(mhc.read, interpret=True))


def _sublayer(module, x, y, target):
    """``F(u) = tanh(u) + y`` between the module and its ``post``."""
    def run(p, x, y):
        u, maps = module.apply({"params": p}, x)
        out = HyperConnection.post(
            x, (jnp.tanh(u.astype(jnp.float32)) + y.astype(jnp.float32)
                ).astype(x.dtype), maps)
        return (out.astype(jnp.float32) * target).sum(), \
            (u, maps.pre, maps.post, maps.res, out)

    return jax.jit(jax.value_and_grad(run, argnums=(1, 2, 0), has_aux=True))


@functools.lru_cache(maxsize=None)
def _both(case):
    """``{name: (kernels, jax.numpy in float32)}`` of a case, float64."""
    n, E, B, S, dtype = CASES[case]
    module = HyperConnection(_config(n, E))
    p = _leaves(module, n, E)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (B, S, n * E), jnp.float32).astype(dtype)
    y = jax.random.normal(ks[1], (B, S, E), jnp.float32).astype(dtype)
    target = jax.random.normal(ks[2], (B, S, n * E), jnp.float32)

    def parts(result):
        (_, values), (dx, dy, dp) = result
        grads = (dx, dy, dp["phi"], dp["a_pre"], dp["a_post"], dp["a_res"],
                 dp["b_pre"], dp["b_post"], dp["b_res"])
        return [np.asarray(t, np.float64) for t in (*values, *grads)]

    with pytest.MonkeyPatch.context() as patch:
        _with_kernels(patch)
        got = parts(_sublayer(module, x, y, target)(p, x, y))
    want = parts(_sublayer(module, x, y, target)(
        p, x.astype(jnp.float32), y.astype(jnp.float32)))
    return dict(zip(VALUES + GRADS, zip(got, want)))


@pytest.mark.parametrize("name", VALUES + GRADS)
@pytest.mark.parametrize("case", CASES)
def test_the_kernels_give_what_the_plain_form_gives(case, name):
    """In float32 to the last bits (the sums add up in another order); a
    bf16 stream to its one rounding, a gain's gradient - one number, a sum
    over every token that cancels - to a few of them."""
    got, want = _both(case)[name]
    assert got.shape == want.shape and np.isfinite(got).all()
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    if CASES[case][4] == jnp.float32:
        assert err <= 2e-5, err
    else:
        assert err <= (6e-2 if name in GAINS else 1e-2), err


@pytest.mark.parametrize("case", CASES)
def test_a_lane_that_is_whole_tiles_takes_the_kernels(case, monkeypatch):
    """``read`` under the guard of one chip books the kernels with the
    shape; ``write`` follows what ``read`` chose."""
    from deepspeed_tpu.ops import attention

    n, E, B, S, dtype = CASES[case]
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(mhc_rows, "read_call", lambda *a, **k: pytest.fail(
        "the guard is asked before any kernel is built"))
    from deepspeed_tpu.comm import mesh as mesh_lib

    mesh_lib.set_mesh(mesh_lib.build_mesh({"dp": 1},
                                          devices=jax.devices()[:1]))
    try:
        plan = mhc._plan(jax.ShapeDtypeStruct((B, S, n * E), dtype), n)
    finally:
        mesh_lib.set_mesh(None)
    assert plan == ("direct", None)
    rows = [r for r in dispatch_report() if r[0] == "mhc_rows"
            and r[1] == "pallas"]
    assert any(f"{n} lanes of {E}; one device" == r[2] for r in rows), rows


@pytest.mark.parametrize("shape,dtype,why", [
    ((1, 64, 4 * 96), jnp.float32, "lanes of 96 channels are no whole lane"),
    ((1, 72, 4 * 128), jnp.float32, "sequence 72 is no whole number"),
    ((1, 64, 5 * 128), jnp.float32, "5 lanes: the rows of numbers hold"),
    ((1, 16, 4 * 128 * 400), jnp.bfloat16, "are past the blocks' VMEM"),
    ((1, 64, 4 * 128), jnp.float16, "a stream of float16"),
    ((1, 64, 4 * 128), jnp.float32, "no TPU"),
])
def test_a_refused_shape_takes_the_plain_form_and_says_why(shape, dtype, why,
                                                           monkeypatch):
    """Off a TPU, or where a lane is no whole tiles, the rows do not group,
    the lanes outnumber the columns or the dtype is neither: ``read`` and
    ``write`` are ``maps`` + ``pre`` and ``post`` to the bit, no kernel is
    built, and ``kernel_dispatch_total{site="mhc_rows"}`` holds the guard's
    words."""
    from deepspeed_tpu.ops import attention

    n = 5 if "5 lanes" in why else 4
    if why != "no TPU":
        monkeypatch.setattr(attention, "on_tpu", lambda: True)
    for call in ("read_call", "post_call"):
        monkeypatch.setattr(mhc_rows, call, lambda *a, **k: pytest.fail(
            "a refused shape reached a kernel"))
    k = mhc_rows.numbers(n)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    y = jax.random.normal(ks[1], (*shape[:2], shape[2] // n)).astype(dtype)
    phi = 0.05 * jax.random.normal(ks[2], (shape[2], k), jnp.float32)
    gains = tuple(jnp.full((1,), a, jnp.float32) for a in (0.7, 0.5, 0.9))
    biases = (jnp.zeros((n,)), jnp.ones((n,)), 2.0 * jnp.eye(n))
    kw = dict(n=n, iters=3, eps=1e-6, clamp=(-10.0, 10.0), rms_eps=1e-6)
    before = {r[:3]: r[3] for r in dispatch_report() if r[0] == "mhc_rows"}

    @jax.jit
    def dispatched(x, y):
        u, made = mhc.read(x, phi, gains, biases, **kw)
        assert made.carried is None
        return u, mhc.write(x, y, made)

    @jax.jit
    def plain(x, y):
        want = mhc.maps(x, phi, gains, biases, **kw)
        return mhc.pre(x, want.pre), mhc.post(x, y, want.res, want.post)

    u, out = dispatched(x, y)
    new = [r[:3] for r in dispatch_report() if r[0] == "mhc_rows"
           and r[3] > before.get(r[:3], 0)]
    assert len(new) == 1 and new[0][1] == "xla" and why in new[0][2], new
    for got, want in zip((u, out), plain(x, y)):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kernel", ["read", "post", "post_back", "read_back"])
def test_two_sublayers_trace_a_body_once(kernel, monkeypatch):
    """A block's two sublayers, forward and gradient under remat: every
    kernel body is traced once a signature (``mhc_rows_traces_total``), not
    once a sublayer and pass."""
    n, E, shape = 4, 128, (1, 32, 4 * 128)
    module = HyperConnection(_config(n, E))
    p = _leaves(module, n, E)
    _with_kernels(monkeypatch)

    @jax.checkpoint
    def block(p, x):
        for _ in range(2):
            u, maps = module.apply({"params": p}, x)
            x = HyperConnection.post(x, jnp.tanh(u), maps)
        return x

    def traces():
        family = get_registry().snapshot().get("mhc_rows_traces_total")
        return {s["labels"]["kernel"]: s["value"]
                for s in (family["samples"] if family else ())
                if str(shape) in s["labels"]["signature"]}

    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    for _ in range(2):
        jax.eval_shape(jax.grad(lambda p, x: block(p, x).sum()), p, x)
    got = traces()
    assert set(got) == {"read", "post", "post_back", "read_back"}, got
    # the plain context and the one under grad, for six calls of the body
    assert 1 <= got[kernel] <= 2, got


@pytest.mark.parametrize("kernel", ["read", "post", "post_back", "read_back"])
@pytest.mark.parametrize("seq,n,E,itemsize,rows", [
    (8192, 4, 3584, 2, {"read": 256, "post": 256, "post_back": 256,
                        "read_back": 128}),
    (64, 2, 128, 4, {"read": 64, "post": 64, "post_back": 64,
                     "read_back": 64}),
    (48, 4, 128, 4, {"read": 16, "post": 16, "post_back": 16,
                     "read_back": 16}),
])
def test_a_block_is_the_most_rows_that_fit(kernel, seq, n, E, itemsize, rows):
    """The twelfth cell's blocks: 256 rows but for the backward of the read
    pass, whose product ``(r dm) phi^T`` is a float32 scratch of the whole
    block; and every block inside the budget."""
    got = mhc_rows.block_rows(kernel, seq, n, E, itemsize)
    assert got == rows[kernel]
    assert got * mhc_rows._row_bytes(kernel, n, E, itemsize) \
        <= mhc_rows._VMEM_BLOCKS < mhc_rows._VMEM_LIMIT


@functools.lru_cache(maxsize=None)
def _over_two_ranks():
    """The gradients of a sublayer's scalar with the batch's two rows on two
    devices of a ``dp`` mesh (the kernels under ``over_batch``'s
    ``shard_map``), and the plain form's with no mesh."""
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.ops import attention

    n, E, B, S = 4, 128, 2, 32
    module = HyperConnection(_config(n, E))
    p = _leaves(module, n, E)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (B, S, n * E), jnp.float32)
    y = jax.random.normal(ks[1], (B, S, E), jnp.float32)
    target = jax.random.normal(ks[2], (B, S, n * E), jnp.float32)

    def leaves(result):
        return [np.asarray(t, np.float64)
                for t in jax.tree_util.tree_leaves(result[1])]

    want = leaves(_sublayer(module, x, y, target)(p, x, y))
    before = {r[:3] for r in dispatch_report() if r[0] == "mhc_rows"}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, "on_tpu", lambda: True)
        patch.setattr(mhc, "read", functools.partial(mhc.read, interpret=True))
        mesh_lib.set_mesh(mesh_lib.build_mesh({"dp": 2},
                                              devices=jax.devices()[:2]))
        try:
            got = leaves(_sublayer(module, x, y, target)(p, x, y))
        finally:
            mesh_lib.set_mesh(None)
    said = {r[:3] for r in dispatch_report() if r[0] == "mhc_rows"} - before
    return got, want, said


@pytest.mark.parametrize("leaf", range(9))
def test_a_batch_over_two_ranks_runs_the_kernels_a_rank(leaf):
    """Under a mesh that splits the batch every call is ``over_batch``'s
    ``shard_map``, inside the rules of the backward too; ``phi`` and the
    maps' leaves go to every rank whole and their gradients add up."""
    got, want, said = _over_two_ranks()
    assert said == {("mhc_rows", "pallas", "4 lanes of 128; shard_map over "
                     "batch axes ('dp',)")}
    err = np.linalg.norm(got[leaf] - want[leaf]) / np.linalg.norm(want[leaf])
    assert got[leaf].shape == want[leaf].shape and err <= 2e-5, err
