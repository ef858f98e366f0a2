"""The Pallas row kernels of the sorted dispatch (``ops/pallas/moe_rows.py``,
PR 32) in interpret mode: each kernel against ``jnp.take`` and against a
dense float32 loop, forward, d-rows and d-weights, for a full permutation
and for a share's partial one (rows past the groups pre-filled with NaN),
at both cells' row widths; the guard's reasons; one trace a signature.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import attention, grouped_matmul as gm
from deepspeed_tpu.ops.pallas import moe_rows
from deepspeed_tpu.ops.pallas.spmd import dispatch_report
from deepspeed_tpu.telemetry import get_registry

S, K = 256, 4
R = S * K
CASES = [(2048, False), (2048, True), (2304, False), (2304, True)]


def _interpreted(monkeypatch):
    """The kernel path on this CPU: the guard sees a TPU, the kernels run
    in the interpreter."""
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    for name in ("pack_rows", "gather_rows", "combine_rows"):
        monkeypatch.setattr(moe_rows, name, functools.partial(
            getattr(moe_rows, name), interpret=True))


def _routing(absent, seed=0):
    """``(order, inv, live)`` of a random dispatch of S tokens x K: a full
    permutation, or a share's (three rows in eight hold a pair)."""
    rng = np.random.default_rng(seed)
    order = rng.permutation(R).astype(np.int32)
    live = R * 3 // 8 if absent else R
    order[live:] = R
    inv = np.full(R, R, np.int32)
    inv[order[:live]] = np.arange(live, dtype=np.int32)
    return jnp.asarray(order), jnp.asarray(inv), live


@functools.lru_cache(maxsize=None)
def _run(M, absent):
    """Everything the cases below compare, computed once a (width, kind):
    the kernel path's forward and gradients, the ``jnp.take`` path's, and
    a dense float32 loop's."""
    mp = pytest.MonkeyPatch()
    order, inv, live = _routing(absent)
    ks = jax.random.split(jax.random.PRNGKey(M + absent), 4)
    x = jax.random.normal(ks[0], (S, M), jnp.float32).astype(jnp.bfloat16)
    y = jax.random.normal(ks[1], (R, M), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.uniform(ks[2], (S, K), jnp.float32)
    ct_rows = jax.random.normal(ks[3], (R, M), jnp.float32)
    ct_tokens = jax.random.normal(ks[3], (S, M), jnp.float32)
    nan_tail = (jnp.arange(R) >= live)[:, None]

    def both(x, y, w):
        rows = gm.repeat_gather(x, order, inv, absent, per_device=True)
        # what the grouped matmul leaves in the rows it skips
        y = jnp.where(nan_tail, jnp.nan, y) if absent else y
        out = gm.combine_rows(y, w, order, inv, absent, per_device=True)
        return rows, out

    def loss(x, y, w):
        rows, out = both(x, y, w)
        return ((rows.astype(jnp.float32) * ct_rows).sum()
                + (out.astype(jnp.float32) * ct_tokens).sum())

    def measure():
        return both(x, y, w), jax.grad(loss, argnums=(0, 1, 2))(x, y, w)

    xla = measure()
    try:
        _interpreted(mp)
        mp.setattr(gm, "_rows_plan", lambda *a: True)   # also when full
        kernel = measure()
    finally:
        mp.undo()

    # the dense float32 loop: pair p = (s, j) sits in row inv[p]
    xf, yf, wf = (np.asarray(a, np.float32) for a in (x, y, w))
    inv_n = np.asarray(inv)
    rows = np.zeros((R, M), np.float32)
    out = np.zeros((S, M), np.float32)
    d_x = np.zeros((S, M), np.float32)
    d_y = np.zeros((R, M), np.float32)
    d_w = np.zeros((S, K), np.float32)
    g_rows, g_tok = np.asarray(ct_rows), np.asarray(ct_tokens)
    for p in np.flatnonzero(inv_n < R):
        s, j, r = p // K, p % K, inv_n[p]
        rows[r] = xf[s]
        out[s] += wf[s, j] * yf[r]
        d_x[s] += g_rows[r]
        d_y[r] = wf[s, j] * g_tok[s]
        d_w[s, j] = g_tok[s] @ yf[r]
    return {"kernel": kernel, "xla": xla,
            "dense": ((rows, out), (d_x, d_y, d_w))}


def _close(a, b, tol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.isfinite(a).all()
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-6)


@pytest.mark.parametrize("against", ["xla", "dense"])
@pytest.mark.parametrize("M,absent", CASES)
def test_forward(M, absent, against):
    """Rows into expert order are exact copies (zeros where a row holds no
    pair); the combine sums in float32 and rounds once."""
    got, want = _run(M, absent)["kernel"][0], _run(M, absent)[against][0]
    assert (np.asarray(got[0], np.float32)
            == np.asarray(want[0], np.float32)).all()
    _close(got[1], want[1], 8e-3)


@pytest.mark.parametrize("against", ["xla", "dense"])
@pytest.mark.parametrize("M,absent", CASES)
def test_d_rows(M, absent, against):
    """d-tokens of the dispatch (a token's k rows summed) and d-rows of
    the combine (the pair's weight times its token's cotangent); a row
    without a pair gets zeros, a pair held elsewhere gives its token
    nothing."""
    got, want = _run(M, absent)["kernel"][1], _run(M, absent)[against][1]
    _close(got[0], want[0], 8e-3)
    _close(got[1], want[1], 8e-3)
    if absent:
        live = _routing(absent)[2]
        assert not np.asarray(got[1], np.float32)[live:].any()


@pytest.mark.parametrize("against", ["xla", "dense"])
@pytest.mark.parametrize("M,absent", CASES)
def test_d_weights(M, absent, against):
    got, want = _run(M, absent)["kernel"][1], _run(M, absent)[against][1]
    # the cotangent reaches either path rounded to bf16; the kernel's
    # products and sums are float32, the XLA path's products bf16
    _close(got[2], want[2], 2e-2 if against == "xla" else 5e-3)
    if absent:
        elsewhere = np.asarray(_routing(absent)[1]).reshape(S, K) == R
        assert not np.asarray(got[2])[elsewhere].any()


def test_pack_rows_skips_blocks_past_the_live_rows():
    """The row form holds a row's two halves in one word; a block wholly
    past ``live`` is not written."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1024, 512),
                          jnp.float32).astype(jnp.bfloat16)
    packed = np.asarray(moe_rows.pack_rows(
        x, jnp.array([300], jnp.int32), name="moe_rows_back",
        interpret=True))[:, 0]
    bits = np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16))
    want = bits[:, :256].astype(np.uint32) | (
        bits[:, 256:].astype(np.uint32) << 16)
    assert (packed[:512] == want[:512]).all()     # two blocks hold live rows


def test_gather_rows_writes_no_block_past_the_live_rows():
    """Going out, a share's buffer is written up to the end of the block
    that holds its last live row - copies, then zeros - and no further:
    the grouped matmul reads no row past its groups."""
    n, k, M, live = 1024, 4, 512, 1100
    x = jax.random.normal(jax.random.PRNGKey(1), (n, M),
                          jnp.float32).astype(jnp.bfloat16)
    rng = np.random.default_rng(1)
    idx = np.full(n * k, n, np.int32)
    idx[:live] = rng.integers(0, n, live)
    packed = moe_rows.pack_rows(x, jnp.array([n], jnp.int32),
                                name="moe_rows_out", interpret=True)
    rows = np.asarray(moe_rows.gather_rows(
        packed, jnp.asarray(idx), jnp.array([live], jnp.int32),
        name="moe_rows_out", interpret=True), np.float32)
    assert (rows[:live] == np.asarray(x, np.float32)[idx[:live]]).all()
    assert not rows[live:2 * moe_rows.STEP].any()


def _reasons():
    return {(impl, reason) for site, impl, reason, _ in dispatch_report()
            if site == "moe_rows"}


@pytest.mark.parametrize("shape,k,dtype,absent,reason", [
    ((256, 2048), 4, jnp.bfloat16, False, "every row holds a pair"),
    ((256, 2048), 4, jnp.float32, True, "rows of float32"),
    ((256, 2176), 4, jnp.bfloat16, True, "row width 2176 is no multiple"),
    ((256, 2048), 3, jnp.bfloat16, True, "top-3 is no power of two"),
    ((192, 2048), 4, jnp.bfloat16, True, "192 tokens x 4 are no whole"),
])
def test_a_refused_shape_falls_back_and_says_why(monkeypatch, shape, k, dtype,
                                                 absent, reason):
    assert not gm._rows_plan(jax.ShapeDtypeStruct(shape, dtype), k, True,
                             absent)
    assert ("xla", "no TPU") in _reasons()
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    assert not gm._rows_plan(jax.ShapeDtypeStruct(shape, dtype), k, True,
                             absent)
    assert any(impl == "xla" and why.startswith(reason)
               for impl, why in _reasons()), _reasons()
    assert not gm._rows_plan(jax.ShapeDtypeStruct((256, 2048), jnp.bfloat16),
                             4, False, True)
    assert ("xla", "global arrays of a mesh of several devices") in _reasons()
    assert gm._rows_plan(jax.ShapeDtypeStruct((256, 2048), jnp.bfloat16), 4,
                         True, True)
    assert ("pallas", "rows 1024 x 2048, block 1024") in _reasons()


def _traces():
    family = get_registry().snapshot().get("moe_rows_traces_total")
    return {(s["labels"]["kernel"], s["labels"]["signature"]): s["value"]
            for s in (family["samples"] if family else ())}


def test_a_model_traces_each_kernel_once_a_signature(monkeypatch):
    """Set-up is designed (ISSUE 32): a 3-layer share of an MoE model's
    ``init``, eval step and train step (remat on), traced in one process,
    enter each kernel's builder once a distinct signature and tracing
    context (the calls sit behind ``jax.jit``, whose cache also keys on
    the context: the plain trace and the one under ``grad`` of a remat
    block differ) - not once a layer and pass, which would be 9 to 21
    entries a kernel - and there are six signatures: the row form of
    (S, M) and of (S k, M), the gather plain and scaled, the combine and
    its d-weights."""
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.parallel.moe import MoEConfig

    from deepspeed_tpu.ops.pallas import spmd

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    # one device's own operands, whatever this host's device count
    monkeypatch.setattr(spmd, "kernel_mesh_plan",
                        lambda *a, **kw: ("direct", None))
    moe = MoEConfig(num_experts=2, routed_experts=8, first_expert=2, top_k=4,
                    drop_tokens=False, norm_topk_prob=True,
                    expert_act="swiglu")
    cfg = LlamaConfig(vocab_size=512, hidden_size=768, num_hidden_layers=3,
                      num_attention_heads=4, intermediate_size=999,
                      moe_intermediate_size=128, max_position_embeddings=512,
                      moe=moe, scan_layers=False, remat=True,
                      dtype=jnp.bfloat16, attn_impl="jnp",
                      vocab_pad_multiple=128)
    model = LlamaForCausalLM(cfg)
    ids = jnp.zeros((1, 512), jnp.int32)
    before = _traces()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]

    def loss(params, ids):
        out = model.apply({"params": params}, ids, labels=ids)
        return out["loss"] if isinstance(out, dict) else out[0]

    jax.make_jaxpr(loss)(params, ids)                       # the eval step
    jax.make_jaxpr(jax.grad(loss))(params, ids)             # the train step
    new = {key: n - before.get(key, 0) for key, n in _traces().items()
           if n - before.get(key, 0)}
    assert new and all(n <= 2 for n in new.values()), new
    assert len(new) == 6, new
    assert {kernel for kernel, _ in new} == {"pack", "gather", "combine"}
