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
    for name in ("pack_rows", "gather_rows", "combine_rows", "swiglu_rows",
                 "swiglu_rows_back"):
        monkeypatch.setattr(moe_rows, name, functools.partial(
            getattr(moe_rows, name), interpret=True))


def _forced(monkeypatch):
    """One device's own operands and a TPU, whatever this host is: the
    guards choose the kernels' path."""
    from deepspeed_tpu.ops.pallas import spmd

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(spmd, "kernel_mesh_plan",
                        lambda *a, **kw: ("direct", None))


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


GLU_ROWS = 4 * moe_rows.GLU
# no row, inside a block, a block's edge, every row
GLU_LIVE = [0, 300, 2 * moe_rows.GLU, GLU_ROWS]


@functools.lru_cache(maxsize=None)
def _swiglu(F, live):
    """Both passes of the SwiGLU row kernels in the interpreter over
    ``[a | b]`` (GLU_ROWS, 2F) whose rows from ``live`` on hold NaN, as
    does ``dh``, and ``jax.numpy``'s float32 arithmetic over the live
    rows."""
    ks = jax.random.split(jax.random.PRNGKey(F + live), 2)
    dead = (jnp.arange(GLU_ROWS) >= live)[:, None]
    ab = jnp.where(dead, jnp.nan, 2 * jax.random.normal(
        ks[0], (GLU_ROWS, 2 * F), jnp.float32)).astype(jnp.bfloat16)
    dh = jnp.where(dead, jnp.nan, jax.random.normal(
        ks[1], (GLU_ROWS, F), jnp.float32)).astype(jnp.bfloat16)
    n = jnp.array([live], jnp.int32)
    got = (moe_rows.swiglu_rows(ab, n, interpret=True),
           moe_rows.swiglu_rows_back(dh, ab, n, interpret=True))

    def plain(ab):
        return jax.nn.silu(ab[:, :F]) * ab[:, F:]

    h, vjp = jax.vjp(plain, ab[:live].astype(jnp.float32))
    want = h, vjp(dh[:live].astype(jnp.float32))[0]
    return ([np.asarray(g, np.float32) for g in got],
            [np.asarray(w.astype(jnp.bfloat16), np.float32) for w in want])


@pytest.mark.parametrize("back", [False, True])
@pytest.mark.parametrize("live", GLU_LIVE)
@pytest.mark.parametrize("F", [512, 768, 896, 1024, 1536])
def test_swiglu_rows(F, live, back):
    """bf16 in, float32 inside, bf16 out, at the cells' expert widths: the
    live rows are ``jax.numpy``'s to a bf16 ulp (no NaN of a dead row
    reaches one)."""
    got, want = (side[back] for side in _swiglu(F, live))
    assert got.shape == (GLU_ROWS, 2 * F if back else F)
    np.testing.assert_allclose(got[:live], want, rtol=2.0 ** -7, atol=1e-30)


def test_swiglu_rows_leaves_the_blocks_past_the_live_rows():
    """Every row of the operands finite: the two blocks that hold the 300
    live rows are written whole, the two past them not at all - forward
    they hold what memory held (in the interpreter NaN), backward what
    ``[a | b]`` held, whose buffer ``d[a | b]`` takes."""
    F, live = 512, 300
    ks = jax.random.split(jax.random.PRNGKey(61), 2)
    ab = jax.random.normal(ks[0], (GLU_ROWS, 2 * F), jnp.bfloat16)
    dh = jax.random.normal(ks[1], (GLU_ROWS, F), jnp.bfloat16)
    n = jnp.array([live], jnp.int32)
    past = 2 * moe_rows.GLU
    h = np.asarray(moe_rows.swiglu_rows(ab, n, interpret=True), np.float32)
    assert np.isfinite(h[:past]).all() and np.isnan(h[past:]).all()
    d = np.asarray(moe_rows.swiglu_rows_back(dh, ab, n, interpret=True),
                   np.float32)
    sentinel = np.asarray(ab, np.float32)
    assert (d[past:] == sentinel[past:]).all()
    assert not (d[:past] == sentinel[:past]).all(axis=1).any()


def test_swiglu_rows_keeps_dead_rows_to_themselves():
    """NaN in the dead rows of ``ab`` and ``dh``, also those that share
    the last live block: the live rows read as with zeros there."""
    F, live = 768, 300
    (h, d), _ = _swiglu(F, live)
    ks = jax.random.split(jax.random.PRNGKey(F + live), 2)
    dead = (jnp.arange(GLU_ROWS) >= live)[:, None]
    ab = jnp.where(dead, 0, 2 * jax.random.normal(
        ks[0], (GLU_ROWS, 2 * F), jnp.float32)).astype(jnp.bfloat16)
    dh = jnp.where(dead, 0, jax.random.normal(
        ks[1], (GLU_ROWS, F), jnp.float32)).astype(jnp.bfloat16)
    n = jnp.array([live], jnp.int32)
    assert np.isfinite(h[:live]).all() and np.isfinite(d[:live]).all()
    assert (h[:live] == np.asarray(moe_rows.swiglu_rows(
        ab, n, interpret=True), np.float32)[:live]).all()
    assert (d[:live] == np.asarray(moe_rows.swiglu_rows_back(
        dh, ab, n, interpret=True), np.float32)[:live]).all()


def _reasons():
    return {(impl, reason) for site, impl, reason, _ in dispatch_report()
            if site == "moe_rows"}


@pytest.mark.parametrize("shape,k,dtype,absent,reason", [
    ((256, 2048), 4, jnp.bfloat16, False, "every row holds a pair"),
    ((256, 2048), 4, jnp.float32, True, "rows of float32"),
    ((256, 2176), 4, jnp.bfloat16, True, "row width 2176 is no multiple"),
    ((256, 2048), 17, jnp.bfloat16, True, "top-17 is not in 1..16"),
    ((256, 2048), 3, jnp.bfloat16, True, "256 tokens x 3 are no whole"),
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
    entries a kernel - and there are eight signatures: the row form of
    (S, M) and of (S k, M), the gather plain and scaled, the combine and
    its d-weights, the SwiGLU between the products and its backward
    (PR 61)."""
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.parallel.moe import MoEConfig

    _forced(monkeypatch)
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
    assert len(new) == 8, new
    assert {kernel for kernel, _ in new} == {"pack", "gather", "combine",
                                             "swiglu", "swiglu_back"}


# ----------------------------------------------------------------------
# PR 46: the starts inside the vector blocks, a group of rows a wait
# ----------------------------------------------------------------------
def _parent_stream(src_ref, stage, sem, count, fetch, trips, work, chunk=0):
    """What PR 32's ``_stream`` did, restated under this tree's signature
    (not the parent's text: its issue loop was unrolled by eight and it
    took one ``consume``): every sub-block's starts in a loop of their own
    before this one's waits, a semaphore wait a row, then the vector work.
    The kernels must equal it bit for bit; ``jnp.take`` and the dense
    float32 loop above are the independent judges, and the parent's own
    module is held to on the chip (``chip_smoke.py row_kernel_times``)."""
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    STEP, SUB = moe_rows.STEP, moe_rows.SUB

    def issue(q, slot):
        stage[slot] = jnp.zeros(stage.shape[1:], stage.dtype)

        def one(t, carry):
            row, at = fetch(q, t)
            pltpu.make_async_copy(src_ref.at[row], stage.at[slot, at],
                                  sem.at[slot]).start()

        lax.fori_loop(0, count(q), one, None)

    def sub_block(q, carry):
        slot = q % 2
        pl.when(q + 1 < STEP // SUB)(lambda: issue(q + 1, 1 - slot))

        def wait(t, carry):
            pltpu.make_async_copy(src_ref.at[0], stage.at[slot, 0],
                                  sem.at[slot]).wait()
            return carry

        lax.fori_loop(0, count(q), wait, 0)
        lax.fori_loop(0, trips, lambda i, c: work(q, slot, i, []), None)
        return carry

    issue(0, 0)
    lax.fori_loop(0, STEP // SUB, sub_block, 0)


def _kernels(parent):
    """``(gather_rows, combine_rows)`` interpreted, jitted anew (no trace
    of the other build answers from jit's cache), under this tree's
    ``_stream`` or the parent's."""
    def fresh(fn):
        inner = fn.__wrapped__

        def call(*a, **k):
            kept = moe_rows._stream
            moe_rows._stream = _parent_stream if parent else kept
            try:
                return inner(*a, interpret=True, **k)
            finally:
                moe_rows._stream = kept

        return jax.jit(call, static_argnames=("name",))

    return fresh(moe_rows.gather_rows), fresh(moe_rows.combine_rows)


# rows 2048: two grid steps of four sub-blocks
ROWS = 2 * moe_rows.STEP
# the gather's live rows: none, the first sub-block alone and not whole, a
# whole sub-block, one more chunk exactly, that less one row, two whole
# sub-blocks (a multiple of SUB), into the second grid step, every row
LIVE = [0, 100, 256, 272, 271, 512, 1324, 2048]
SHAPES = [(2048, 4), (2304, 8)]     # the seventh cell's and Mellum 2's


def _pairs_of(k):
    """Pairs with a row in each of the combine's eight sub-blocks: none,
    one group of rows a wait exactly, a group less one, every pair, a
    quarter (a share's), one pair more, a few, some."""
    return [0, moe_rows.WAIT, moe_rows.WAIT - 1, moe_rows.SUB,
            moe_rows.SUB // 4, moe_rows.SUB // 4 + 1, 7, 100]


@functools.lru_cache(maxsize=None)
def _both_gathers(M):
    """``{(live, scaled): (new, parent)}``: the rows each build writes."""
    S = 512
    rng = np.random.default_rng(M)
    x = jax.random.normal(jax.random.PRNGKey(M), (S, M),
                          jnp.float32).astype(jnp.bfloat16)
    packed = moe_rows.pack_rows(x, jnp.array([S], jnp.int32),
                                name="moe_rows_out", interpret=True)
    scale = jnp.asarray(rng.random((ROWS, 1)), jnp.float32)
    gathers = _kernels(False)[0], _kernels(True)[0]
    out = {}
    for live in LIVE:
        idx = np.full(ROWS, S, np.int32)
        idx[:live] = rng.integers(0, S, live)
        written = -(-live // moe_rows.STEP) * moe_rows.STEP
        for scaled in (False, True):
            got = [np.asarray(gather(
                packed, jnp.asarray(idx), jnp.array([live], jnp.int32),
                scale if scaled else None, name="moe_rows_out"),
                np.float32)[:written]
                for gather in gathers]
            want = np.asarray(x, np.float32)[idx[:live]]
            if scaled:
                want = (want * np.asarray(scale)[:live]).astype(
                    jnp.bfloat16).astype(np.float32)
            out[live, scaled] = (*got, want)
    return out


@pytest.mark.parametrize("scaled", [False, True])
@pytest.mark.parametrize("live", LIVE)
@pytest.mark.parametrize("M", [M for M, _ in SHAPES])
def test_gather_equals_the_parents_bit_for_bit(M, live, scaled):
    """At a quarter of each cell's row width, in the whole 256 channels the
    kernels take (512 and 768; the ids keep the cells' 2048 and 2304): which rows the stream moves, in
    which order and behind which wait is what the two builds could differ
    in, and no row's width enters it; interpreted, a call's time goes by the
    bytes it copies (PR 52: 155 s and 142 s of the suite at the full widths,
    a tenth of that here)."""
    new, parent, want = _both_gathers(-(-M // 1024) * 256)[live, scaled]
    assert new.shape == parent.shape and new.shape[0] >= live
    np.testing.assert_array_equal(new, parent)
    np.testing.assert_array_equal(new[:live], want)
    assert not new[live:].any()


@functools.lru_cache(maxsize=None)
def _both_combines(M, k):
    """``(counts, {dw: (new, parent)})`` of one call whose sub-blocks hold
    :func:`_pairs_of` pairs with a row; the rows past them hold NaN."""
    S = ROWS // k
    rng = np.random.default_rng(M + k)
    counts = _pairs_of(k)
    n_live = sum(counts)
    rows = rng.permutation(n_live).astype(np.int32)
    idx = np.full(ROWS, ROWS, np.int32)
    at = 0
    for q, n in enumerate(counts):
        where = q * moe_rows.SUB + rng.permutation(moe_rows.SUB)[:n]
        idx[where] = rows[at:at + n]
        at += n
    ks = jax.random.split(jax.random.PRNGKey(M + k), 3)
    y = jax.random.normal(ks[0], (ROWS, M), jnp.float32)
    y = jnp.where((jnp.arange(ROWS) >= n_live)[:, None], jnp.nan, y).astype(
        jnp.bfloat16)
    w = jax.random.uniform(ks[1], (S, k), jnp.float32)
    g = jax.random.normal(ks[2], (S, M), jnp.float32).astype(jnp.bfloat16)
    packed = moe_rows.pack_rows(y, jnp.array([n_live], jnp.int32),
                                name="moe_rows_back", interpret=True)
    combines = _kernels(False)[1], _kernels(True)[1]
    out = {dw: tuple(
        np.asarray(combine(packed, jnp.asarray(idx), w, g if dw else None,
                           name="moe_rows_back"), np.float32)
        for combine in combines)
        for dw in (False, True)}
    return counts, out


@pytest.mark.parametrize("dw", [False, True])
@pytest.mark.parametrize("sub_block", range(8))
@pytest.mark.parametrize("M,k", SHAPES)
def test_combine_equals_the_parents_bit_for_bit(M, k, sub_block, dw):
    """Forward (and, over ones, the dispatch's d-tokens) and d-weights, the
    tokens of one sub-block at a time: each holds another count of pairs
    with a row, on either side of what decides how its rows are awaited."""
    counts, out = _both_combines(M, k)
    new, parent = out[dw]
    tokens = moe_rows.SUB // k
    mine = slice(sub_block * tokens, (sub_block + 1) * tokens)
    assert np.isfinite(new[mine]).all()
    np.testing.assert_array_equal(new[mine], parent[mine])
    assert bool(new[mine].any()) == bool(counts[sub_block])


@pytest.mark.parametrize("live", LIVE)
def test_starts_are_counted_where_the_kernel_starts_them(live):
    """``moe_rows_dma_starts_total``'s numbers: the gather starts a grid
    step's first sub-block in a loop and hides whole chunks of the others
    inside the trips before them."""
    per = [min(max(live - q * 256, 0), 256) for q in range(ROWS // 256)]
    block = sum(n // 16 * 16 for q, n in enumerate(per) if q % 4)
    assert moe_rows.gather_starts(live, ROWS) == (block, live - block)


def test_the_probes_book_the_starts_from_a_steps_counts(monkeypatch):
    """``scripts/probe_mellum2_scopes.py count_row_dma_starts`` stands in
    front of ``record_stats``: a layer's held pairs are its gather's live
    rows; a layer that holds every expert books nothing."""
    import os

    from deepspeed_tpu.parallel import moe

    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        "scripts"))
    import probe_mellum2_scopes

    def read():
        family = get_registry().snapshot().get("moe_rows_dma_starts_total")
        return {(s["labels"]["kernel"], s["labels"]["where"]): s["value"]
                for s in (family["samples"] if family else ())}

    monkeypatch.setattr(moe, "record_stats", moe.record_stats)
    probe_mellum2_scopes.count_row_dma_starts()
    before = read()
    stats = {"tokens_per_expert": np.full((2, 4), ROWS // 4),
             "dropped": np.zeros(2), "balance_loss": np.zeros(2),
             "router_z": np.zeros(2)}
    moe.record_stats(stats)
    assert read() == before
    moe.record_stats(dict(stats, elsewhere=np.array([ROWS - 272,
                                                     ROWS - 1324])))
    new = {key: n - before.get(key, 0) for key, n in read().items()}
    want = [moe_rows.gather_starts(live, ROWS) for live in (272, 1324)]
    assert new == {("gather", "block"): want[0][0] + want[1][0],
                   ("gather", "loop"): want[0][1] + want[1][1]}


# sha256 of what one expert layer with EVERY expert held (OLMoE's kind: a
# full permutation, which the guards leave to XLA's gathers, a product each
# for gate and up and XLA's SwiGLU) traces to on a TPU, forward + backward
# with its statistics.  Re-pinned by PR 61 (d81e2655... at its parent,
# 5f07282): the grouped matmul's backward is now ``ops/grouped_matmul.py
# _megablox_bwd``, the same two kernels with the weights' gradient first;
# nothing else of PRs 46 and 61 reaches a program without the row kernels
PARENT_FULL_LAYER = \
    "18e851c7d67d23dc5f13de994fec0b63b8bbaeea921213b1d8525b6cc5591fb0"


@pytest.mark.parametrize("share", [False, True])
def test_a_layers_program_counts_no_starts(monkeypatch, share):
    """The counter is the probes' (on the host): a share's layer returns
    the parent's statistics and no more, and a layer that holds every
    expert traces to the parent's program."""
    import hashlib
    import re

    from deepspeed_tpu.parallel.moe import MoEConfig, MoELayer

    _forced(monkeypatch)
    held = dict(routed_experts=32, first_expert=8) if share else {}
    cfg = MoEConfig(num_experts=8, top_k=8 if share else 4,
                    drop_tokens=False, norm_topk_prob=True,
                    expert_act="swiglu", **held)
    layer = MoELayer(cfg, model_dim=2048, hidden_dim=128, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16)
    p = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"]

    def loss(p, x):
        out, aux, stats = layer.apply({"params": p}, x, train=True,
                                      return_stats=True)
        return out.astype(jnp.float32).sum() + aux, stats

    if share:
        stats = jax.eval_shape(loss, p, x)[1]
        assert set(stats) == {"tokens_per_expert", "dropped", "balance_loss",
                              "router_z", "elsewhere"}
        return
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, has_aux=True))(p, x)
    text = re.sub(r" at /\S+:\d+", "", str(jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_FULL_LAYER


def _kernel_calls(fn, *args):
    """``{name: count}`` of the kernel calls ``fn`` traces to, inner
    jaxprs included: a ``pallas_call`` of this repo by its own name, one
    of megablox's (which names none) by the ``jax.jit`` around it."""
    import collections

    from jax.extend import core as jex

    counts = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call" or (
                    eqn.primitive.name in ("jit", "pjit")
                    and eqn.params["name"] in ("gmm", "tgmm")):
                counts[eqn.params["name"]] += 1
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) \
                        else (value,):
                    if isinstance(sub, jex.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jex.Jaxpr):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return counts


@pytest.mark.parametrize("share", [False, True])
def test_a_share_keeps_one_buffer_between_its_products(monkeypatch, share):
    """PR 61: a share's SwiGLU layer multiplies by ``[gate | up]`` once and
    runs the row kernel over ``[a | b]`` - 2 ``gmm`` forward, 2 ``gmm`` and
    2 ``tgmm`` backward, where a product each makes 3, 3 and 3 - and a
    layer that holds every expert keeps a product each; the guard that
    decided is in ``kernel_dispatch_total{site="moe_swiglu"}``."""
    from deepspeed_tpu.parallel.moe import MoEConfig, MoELayer

    _forced(monkeypatch)
    held = dict(routed_experts=32, first_expert=8) if share else {}
    cfg = MoEConfig(num_experts=8, top_k=8 if share else 4,
                    drop_tokens=False, norm_topk_prob=True,
                    expert_act="swiglu", **held)
    layer = MoELayer(cfg, model_dim=2048, hidden_dim=128, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16)
    p = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"]

    def loss(p, x):
        out, aux = layer.apply({"params": p}, x, train=True)
        return out.astype(jnp.float32).sum() + aux

    forward = _kernel_calls(loss, p, x)
    both = _kernel_calls(jax.value_and_grad(loss, argnums=(0, 1)), p, x)
    products = {name: (forward[name], both[name] - forward[name])
                for name in ("gmm", "tgmm")}
    rows = {name: both[name] for name in both if "swiglu" in str(name)}
    said = {(impl, reason) for site, impl, reason, _ in dispatch_report()
            if site == "moe_swiglu"}
    if share:
        assert products == {"gmm": (2, 2), "tgmm": (0, 2)}
        assert rows == {"moe_swiglu_rows": 1, "moe_swiglu_rows_back": 1}
        assert ("pallas", "rows 16384 x 256, block 256") in said
    else:
        assert products == {"gmm": (3, 3), "tgmm": (0, 3)}
        assert not rows
        assert ("xla", "every row holds a pair") in said


@functools.lru_cache(maxsize=None)
def _layer_both_ways(share):
    """One SwiGLU layer's output and gradients (of ``x``, the router's
    ``wg`` - through the routing weights - ``gate``, ``up``, ``down``) on
    the kernels' path and with a product each, and its variables' tree
    both ways.  The grouped matmul is ``ragged_dot`` with NaN in the rows
    past the groups, which the kernel leaves as memory held them."""
    import flax.linen as nn

    from deepspeed_tpu.parallel import moe

    mp = pytest.MonkeyPatch()
    held = dict(routed_experts=16, first_expert=4) if share else {}
    cfg = moe.MoEConfig(num_experts=4, top_k=4 if share else 2,
                        drop_tokens=False, norm_topk_prob=True,
                        expert_act="swiglu", **held)
    layer = moe.MoELayer(cfg, model_dim=256, hidden_dim=128,
                         dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 256), jnp.bfloat16)
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape, jnp.float32)

    def matmul(a, w, sizes, per_device=None):
        out = jax.lax.ragged_dot(a, w, sizes)
        past = (jnp.arange(a.shape[0]) >= sizes.sum())[:, None]
        return jnp.where(past, jnp.nan, out)

    def measure():
        variables = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
        params = nn.unbox(variables)["params"]

        def loss(params, x):
            out, aux = layer.apply({"params": params}, x, train=True)
            return (out.astype(jnp.float32) * ct).sum() + aux, out

        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                             has_aux=True)(params, x)
        tree = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
        return out, grads, (tree, nn.get_partition_spec(variables))

    try:
        _forced(mp)
        _interpreted(mp)
        mp.setattr(moe, "grouped_matmul", matmul)
        before = dict((r[:3], r[3]) for r in dispatch_report())
        one = measure()
        said = {r[1:3] for r in dispatch_report()
                if r[0] == "moe_swiglu" and r[3] > before.get(r[:3], 0)}
        mp.setattr(moe, "swiglu_plan", lambda *a: False)
        each = measure()
    finally:
        mp.undo()
    return one, each, said


@pytest.mark.parametrize("what", ["out", "x", "wg", "gate", "up", "down",
                                  "tree"])
@pytest.mark.parametrize("share", [False, True])
def test_one_buffer_equals_a_product_each(share, what):
    """The layer with ``[gate | up]`` and the row kernel against the same
    layer with three products and XLA's ``silu(a) * b``, to bf16
    tolerance; one tree of parameters (names, shapes, partitioning) serves
    both."""
    (out, (d_p, d_x), tree), (out3, (d_p3, d_x3), tree3), said = \
        _layer_both_ways(share)
    assert {impl for impl, _ in said} == ({"pallas"} if share else {"xla"})
    if what == "tree":
        assert tree == tree3
        assert set(d_p["experts"]) == {"gate", "up", "down"}
    elif what == "out":
        _close(out, out3, 2e-2)
    elif what == "x":
        _close(d_x, d_x3, 2e-2)
    elif what == "wg":
        _close(d_p["gate"]["wg"], d_p3["gate"]["wg"], 2e-2)
    else:
        _close(d_p["experts"][what], d_p3["experts"][what], 2e-2)


@functools.lru_cache(maxsize=None)
def _ordered_backward():
    import importlib

    backend = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    mp = pytest.MonkeyPatch()
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(ks[0], (512, 256), jnp.float32)
    rhs = jax.random.normal(ks[1], (4, 256, 128), jnp.float32)
    ct = jax.random.normal(ks[2], (512, 128), jnp.float32)
    sizes = jnp.array([100, 0, 300, 50], jnp.int32)    # 62 rows past them

    def grads(matmul):
        return jax.grad(lambda a, b: (matmul(a, b) * ct).sum(),
                        argnums=(0, 1))(lhs, rhs)

    try:
        for name in ("gmm", "tgmm"):
            mp.setattr(backend, name, functools.partial(
                getattr(backend, name), interpret=True))
        got = grads(lambda a, b: gm._megablox(a, b, sizes, (128, 128, 128)))
    finally:
        mp.undo()
    want = grads(lambda a, b: jax.lax.ragged_dot(a, b, sizes,
                                                 precision="highest"))
    return [(np.asarray(g), np.asarray(w)) for g, w in zip(got, want)]


@pytest.mark.parametrize("which", [0, 1], ids=["d_rows", "d_weights"])
def test_the_grouped_matmuls_ordered_backward(which):
    """PR 61: ``_megablox_bwd`` runs megablox's two backward kernels, the
    weights' gradient first and the rows' behind a barrier; both are
    ``ragged_dot``'s own, uneven and empty groups included, over the rows
    of the groups (the kernel leaves the rows past them alone)."""
    got, want = _ordered_backward()[which]
    live = 450 if which == 0 else None
    _close(got[:live], want[:live], 1e-5)
