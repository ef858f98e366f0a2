#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, four phases, no failure caught (the exit code is the run's):

1. *kernels*  — every Pallas kernel on the default TPU path compiled by
   Mosaic (``interpret=False``) at the shapes the two models below use and
   compared with the reference that sits beside it.
2. *trainer*  — GPT-2-1.5B at full width (ZeRO-3, ``adamw8bit``, unrolled
   stack, micro 2, ``dots_saveable+flash`` remat, chunked head) through
   ``deepspeed_tpu.initialize`` → ``init_params`` → ``prepare_batch`` →
   ``train_batch``; every loss finite, the last below the first on the
   repeated batch, the step built on the flash kernel.
3. *server*   — gpt2-760m at full width through ``init_inference`` (prefix
   cache, 64-token pages) → ``ContinuousBatcher(n_slots=8)`` →
   ``warmup_windows`` → ``run``: every request answered in full, paged,
   fused decode engaged, zero gathers, zero fallbacks, zero leaks.
4. *dispatch* — one line per kernel dispatch site saying what it resolved
   to and why; a site whose guard said "supported" but that ran a
   reference or an interpreted kernel fails the run.

Random weights make logits too flat for token equality to mean anything,
so numerics are judged in phase 1 (tolerance ``TOL`` below) and phases 2-3
judge control flow, kernel engagement and finiteness.

Exits non-zero, printing no result line, when JAX finds no TPU.  The last
line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
import gc
import json
import os
import sys
import time

# Kernel outputs are bf16 (8 mantissa bits); the references run in fp32 at
# "highest" matmul precision on the same bf16 inputs.  An output passes
# when  max|kernel - ref| <= TOL * max|ref|  — about five bf16 ulps of
# the largest value, far below any indexing or masking error (those are
# O(1) relative).
TOL = 2e-2

# Adam without warm-up overshoots in its first updates, so the third loss
# alone is no judge: measured on the chip (PR 21) the repeated batch reads
# 11.17, 10.51, 11.78, 10.33, 10.30, 10.12, 9.97, 9.78 and falls
# monotonically from there.  Eight single-step calls, last against first.
TRAIN_STEPS = 8
N_SLOTS = 8
PAGE_TOKENS = 64
NEW_TOKENS = 64
SERVE_TICKS = 16


def _check_close(name, got, ref):
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {ref.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite kernel output")
    err = float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-12))
    print(f"  {name}: rel-to-max err {err:.2e} (tol {TOL:.0e})", flush=True)
    if err > TOL:
        raise AssertionError(f"{name}: err {err:.3e} > {TOL}")


# ---------------------------------------------------------------------------
# phase 1: kernels
# ---------------------------------------------------------------------------

PARENT_CHECKOUT = ".chip_archive/parent"    # where a builder unpacks one


def _diff_from_the_parents(name, names, got, run,
                           module="flash_attention.py"):
    """Where a checkout of the parent commit is unpacked beside this file
    (``git archive <parent> | tar -x -C .chip_archive/parent``): print the
    largest difference of each of ``got`` (named ``names``) from what
    ``run(fa)`` gives with ``fa`` that checkout's flash module (or another
    ``module`` of its kernels)."""
    import jax.numpy as jnp

    root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        PARENT_CHECKOUT)
    if not os.path.isdir(root):
        print(f"  {name}: no parent checkout at {PARENT_CHECKOUT}, nothing "
              f"to compare with", flush=True)
        return
    old = run(_load_pallas_module("parent", root, module))
    diffs = {n: float(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)).max())
             for n, a, b in zip(names, got, old)}
    print(f"  {name}: largest difference from the parent's kernels "
          + json.dumps(diffs), flush=True)


def _out_and_grads(fn, loss, args):
    """``fn(*args)`` and the gradients of ``loss(fn, *args)``, each jitted."""
    import jax

    return (jax.jit(fn)(*args), *jax.jit(jax.grad(
        lambda *a: loss(fn, *a), argnums=range(len(args))))(*args))


def kernel_flash():
    """Flash forward + backward at the 1.5B trainer's per-micro shape."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.attention import _jnp_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    shape = (2, 1024, 25, 64)
    q, k, v, ct = (jax.random.normal(kk, shape, jnp.float32)
                   .astype(jnp.bfloat16) for kk in ks)

    def loss(fn, q, k, v):
        return (fn(q, k, v).astype(jnp.float32)
                * ct.astype(jnp.float32)).sum()

    flash = lambda q, k, v: flash_attention(          # noqa: E731
        q, k, v, causal=True, block_q=512, block_k=512)
    ref = lambda q, k, v: _jnp_attention(             # noqa: E731
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True, bias=None, mask=None, dropout_rate=0.0,
        dropout_rng=None, scale=None)
    out = jax.jit(flash)(q, k, v)
    grads = jax.jit(jax.grad(lambda *a: loss(flash, *a), argnums=(0, 1, 2))
                    )(q, k, v)
    with jax.default_matmul_precision("highest"):
        out_r = jax.jit(ref)(q, k, v)
        grads_r = jax.jit(jax.grad(lambda *a: loss(ref, *a),
                                   argnums=(0, 1, 2)))(q, k, v)
    _check_close("flash fwd (2,1024,25,64) 512x512", out, out_r)
    for n, g, gr in zip(("dq", "dk", "dv"), grads, grads_r):
        _check_close(f"flash bwd {n}", g, gr)

    _diff_from_the_parents(
        "flash (2,1024,25,64)", ("fwd", "dq", "dk", "dv"), (out, *grads),
        lambda fa: _out_and_grads(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, block_q=512, block_k=512), loss, (q, k, v)))


def kernel_mxu_operand_rounding(reps: int = 5):
    """What the MXU does with a float32 operand under Mosaic's default
    contract precision (PR 47): the products a flash tile makes, each with
    float32 operands (bf16 values widened, float32 probabilities) and with
    the operands in bf16, compared bit for bit and timed a call from one
    profiler trace.  ``qk`` is ``a · bᵀ`` of two bf16 ``(512, 128)`` tiles,
    ``pv`` is ``p · b`` with a float32 ``p (512, 512)``, ``pTa`` is ``pᵀ · a``
    (the transposed contraction of the backward's ``pᵀ · dO``, ``dSᵀ · q``).
    A product is made 16 times a program over the tiles of a resident
    ``(16 · 512, 128)`` operand and summed in float32, as a sweep does; ``p``
    is another a tile (``p + t``, a float32 sum), so its cast is made a
    tile, as a kernel's is.
    Where the two forms differ, the float32 form's and the bf16 form's
    distance from the float64 product of the UNROUNDED operands say whether
    the chip multiplies float32 at more than bf16 precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    T, G, N, D = 16, 32, 512, 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(47), 3)
    a = jax.random.normal(ks[0], (G * N, D), f32).astype(bf16)
    b = jax.random.normal(ks[1], (T * N, D), f32).astype(bf16)
    p = jax.random.uniform(ks[2], (G * N, N), f32)

    def dot(x, y, contract):
        return jax.lax.dot_general(x, y, (contract, ((), ())),
                                   preferred_element_type=f32)

    def body(form, narrow):
        def cast(x):        # the operand's type: float32 (today) or bf16
            return x.astype(bf16 if narrow else f32)

        def kernel(a_ref, b_ref, p_ref, o_ref):
            acc = jnp.zeros(o_ref.shape, f32)
            for t in range(T):
                tile = b_ref[pl.ds(t * N, N)]
                if form == "qk":
                    acc += dot(cast(a_ref[...]), cast(tile), ((1,), (1,)))
                    continue
                p_t = cast(p_ref[...] + f32(t))
                acc += dot(p_t, cast(tile),
                           ((1,), (0,)) if form == "pv" else ((0,), (0,)))
            o_ref[...] = acc
        return kernel

    calls = {}
    for form, width in (("qk", N), ("pv", D), ("pTa", D)):
        for narrow in (False, True):
            name = f"mxu_{form}_{'bf16' if narrow else 'f32'}"
            calls[name] = jax.jit(pl.pallas_call(
                body(form, narrow), grid=(G,),
                in_specs=[pl.BlockSpec((N, D), lambda g: (g, 0)),
                          pl.BlockSpec((T * N, D), lambda g: (0, 0)),
                          pl.BlockSpec((N, N), lambda g: (g, 0))],
                out_specs=pl.BlockSpec((N, width), lambda g: (g, 0)),
                out_shape=jax.ShapeDtypeStruct((G * N, width), f32),
                name=name))
    got = {n: np.asarray(jax.block_until_ready(fn(a, b, p)))
           for n, fn in calls.items()}

    # float64 products of the first program's operands as they are
    a64, b64 = (np.asarray(x, np.float64) for x in (a[:N], b))
    tiles = b64.reshape(T, N, D)
    p_ts = [np.asarray(np.asarray(p[:N]) + np.float32(t), np.float64)
            for t in range(T)]
    exact = {"qk": sum(a64 @ t.T for t in tiles),
             "pv": sum(x @ t for x, t in zip(p_ts, tiles)),
             "pTa": sum(x.T @ t for x, t in zip(p_ts, tiles))}
    verdict = {}
    for form in ("qk", "pv", "pTa"):
        wide, narrow = got[f"mxu_{form}_f32"], got[f"mxu_{form}_bf16"]
        line = verdict[form] = {
            "bitwise_equal": bool(np.array_equal(wide, narrow)),
            "max_abs_diff": float(np.abs(wide - narrow).max()),
            "entries_differing": int(np.count_nonzero(wide != narrow)),
            "max_abs": float(np.abs(wide).max())}
        for tag, res in (("f32", wide), ("bf16", narrow)):
            line[f"{tag}_err_to_float64_of_unrounded"] = float(
                np.abs(res[:N] - exact[form]).max())

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chiprun_out", "mxu_operand_rounding")
    os.makedirs(out, exist_ok=True)
    with jax.profiler.trace(out):
        for _ in range(reps):
            for fn in calls.values():
                jax.block_until_ready(fn(a, b, p))
    times = _traced_op_times(out)
    for name in calls:
        durs = [d for n, ds in times.items() if name in n for d in ds]
        form = name.split("_")[1]
        # microseconds a (512 x 512 x 128) product
        verdict[form][f"{name.split('_')[2]}_us_a_product"] = (
            float(np.median(durs)) / 1e3 / (G * T) if durs else None)
    for form, line in verdict.items():
        print(f"  [mxu operand rounding] {form}: " + json.dumps(line),
              flush=True)
    return verdict


def _decode_ref(q, k_cache, v_cache, lengths):
    """The masked jnp attention ``cached_decode_attention`` falls back to."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.attention import _jnp_attention

    k_pos = jnp.arange(k_cache.shape[1])[None, None, None, :]
    mask = k_pos < lengths[:, None, None, None]
    return _jnp_attention(
        q.astype(jnp.float32), k_cache.astype(jnp.float32),
        v_cache.astype(jnp.float32), causal=False, bias=None, mask=mask,
        dropout_rate=0.0, dropout_rng=None, scale=None)


def kernel_decode_attention():
    """gpt2-760m decode rows: the streamed path (a 1024-token K+V panel at
    KV=16, D=96 is 6.3 MB against the 4 MB budget) and one whole-panel
    shape."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.decode_attention import (decode_attention,
                                                           fits_vmem)

    for s_max, lengths in ((1024, (1, 64, 65, 500, 777, 1000, 1023, 1024)),
                           (256, (1, 7, 64, 65, 128, 200, 255, 256))):
        ks = jax.random.split(jax.random.PRNGKey(s_max), 3)
        q = jax.random.normal(ks[0], (8, 1, 16, 96), jnp.float32
                              ).astype(jnp.bfloat16)
        kc = jax.random.normal(ks[1], (8, s_max, 16, 96), jnp.float32
                               ).astype(jnp.bfloat16)
        vc = jax.random.normal(ks[2], (8, s_max, 16, 96), jnp.float32
                               ).astype(jnp.bfloat16)
        lens = jnp.asarray(lengths, jnp.int32)
        path = "panel" if fits_vmem(s_max, 16, 96, 2) else "streamed"
        out = jax.jit(decode_attention)(q, kc, vc, lens)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(_decode_ref)(q, kc, vc, lens)
        _check_close(f"decode_attention S={s_max} KV=16 D=96 ({path})",
                     out, ref)


def kernel_paged_attention():
    """Paged decode at the server's page geometry: ragged lengths that
    straddle a page boundary, one row of length 1, pages scattered over
    the arena."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, paged_reference_attention)

    B, T, pt, KV, D = 8, 16, PAGE_TOKENS, 16, 96
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    n_pages = B * T + 1
    q = jax.random.normal(ks[0], (B, 1, KV, D), jnp.float32
                          ).astype(jnp.bfloat16)
    kp = jax.random.normal(ks[1], (n_pages, pt, KV, D), jnp.float32
                           ).astype(jnp.bfloat16)
    vp = jax.random.normal(ks[2], (n_pages, pt, KV, D), jnp.float32
                           ).astype(jnp.bfloat16)
    table = jnp.asarray(np.random.default_rng(0).permutation(n_pages - 1)
                        .reshape(B, T).astype(np.int32))
    lens = jnp.asarray((1, 63, 64, 65, 130, 500, 1000, 1024), jnp.int32)
    out = jax.jit(paged_decode_attention)(q, kp, vp, table, lens)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda *a: paged_reference_attention(
            a[0].astype(jnp.float32), a[1].astype(jnp.float32),
            a[2].astype(jnp.float32), a[3], a[4]))(q, kp, vp, table, lens)
    _check_close(f"paged_decode_attention pt={pt} KV=16 D=96", out, ref)


def _dequant(codes, scale):
    import jax.numpy as jnp

    G = scale.shape[0]
    K, N = codes.shape
    return (codes.reshape(G, K // G, N).astype(jnp.float32)
            * scale[:, None, :]).reshape(K, N)


def kernel_decode_layer():
    """The two decode megakernels at gpt2-760m widths, bf16 and W8A16."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.decode_layer import (
        fused_norm_proj, fused_post_attn, norm_proj_supported,
        post_attn_supported, reference_norm_proj, reference_post_attn)
    from deepspeed_tpu.ops.w8 import quantize_weight

    rows, E, F = N_SLOTS, 1536, 6144
    ks = jax.random.split(jax.random.PRNGKey(3), 12)
    bf = jnp.bfloat16

    def rnd(k, shape, std=1.0, dtype=bf):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    x, y = rnd(ks[0], (rows, E)), rnd(ks[1], (rows, E))
    ns = 1.0 + rnd(ks[2], (E,), 0.1, jnp.float32)
    nb = rnd(ks[3], (E,), 0.1, jnp.float32)
    w_qkv, b_qkv = rnd(ks[4], (E, 3 * E), 0.02), rnd(ks[5], (3 * E,), 0.02)
    wo, bo = rnd(ks[6], (E, E), 0.02), rnd(ks[7], (E,), 0.02)
    w1, b1 = rnd(ks[8], (E, F), 0.02), rnd(ks[9], (F,), 0.02)
    w2, b2 = rnd(ks[10], (F, E), 0.02), rnd(ks[11], (E,), 0.02)

    for quant in (False, True):
        tag = "W8A16" if quant else "bf16"
        if quant:
            pack = lambda w: quantize_weight(w.astype(jnp.float32), 128)  # noqa: E731
            unpack = lambda p: _dequant(*p)                               # noqa: E731
        else:
            pack = lambda w: w                                            # noqa: E731
            unpack = lambda w: w.astype(jnp.float32)                      # noqa: E731
        g_e, g_f = (E // 128, F // 128) if quant else (1, 1)
        assert norm_proj_supported(rows, E, 3 * E, 2, quant, g_e)
        assert post_attn_supported(rows, E, F, 2, quant, g_e, g_f)
        pq, po, p1, p2 = pack(w_qkv), pack(wo), pack(w1), pack(w2)
        qkv = jax.jit(lambda x, w: fused_norm_proj(x, ns, nb, w, b_qkv)
                      )(x, pq)
        out = jax.jit(lambda y, x, wo, w1, w2: fused_post_attn(
            y, x, wo, bo, ns, nb, (w1, b1, w2, b2)))(y, x, po, p1, p2)
        with jax.default_matmul_precision("highest"):
            qkv_r = jax.jit(lambda x, w: reference_norm_proj(
                x, ns, nb, w, b_qkv))(x, unpack(pq))
            out_r = jax.jit(lambda y, x, wo, w1, w2: reference_post_attn(
                y, x, wo, bo, ns, nb, (w1, b1, w2, b2)))(
                    y, x, unpack(po), unpack(p1), unpack(p2))
        _check_close(f"fused_norm_proj rows={rows} E={E} N={3 * E} {tag}",
                     qkv, qkv_r)
        _check_close(f"fused_post_attn rows={rows} E={E} F={F} {tag}",
                     out, out_r)


def kernel_w8_matmul():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.w8_matmul import (supported,
                                                    w8a16_matmul_pallas)
    from deepspeed_tpu.ops.w8 import quantize_weight

    for K, N in ((1536, 6144), (6144, 1536)):
        ks = jax.random.split(jax.random.PRNGKey(K), 2)
        x = jax.random.normal(ks[0], (N_SLOTS, K), jnp.float32
                              ).astype(jnp.bfloat16)
        codes, scale = quantize_weight(
            0.02 * jax.random.normal(ks[1], (K, N), jnp.float32), 128)
        assert supported(x.shape, codes.shape, scale.shape[0], mesh_ok=True)
        out = jax.jit(w8a16_matmul_pallas)(x, codes, scale)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda x, c, s: jnp.dot(
                x.astype(jnp.float32), _dequant(c, s)))(x, codes, scale)
        _check_close(f"w8_matmul ({N_SLOTS},{K})x({K},{N}) g=128", out, ref)


def kernel_grouped_matmul():
    """The expert matmuls of OLMoE-1B-7B's train step (8192 tokens x top-8
    rows over 64 experts) through ``ops.grouped_matmul``: forward (``gmm``),
    d-rows (``gmm`` transposed) and d-weights (``tgmm``), with uneven groups
    off the 512-row tile and two experts nobody chose, against a loop over
    the groups' own row slices in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.grouped_matmul import grouped_matmul
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    rows, experts = 8192 * 8, 64
    rng = np.random.default_rng(26)
    share = rng.dirichlet(np.full(experts, 0.7))
    share[[5, 63]] = 0.0                                # empty groups
    sizes = np.floor(share / share.sum() * rows).astype(np.int64)
    sizes[int(np.argmax(sizes))] += rows - sizes.sum()
    assert sizes.sum() == rows and (sizes[[5, 63]] == 0).all()
    ends = np.cumsum(sizes)
    print(f"  grouped_matmul groups: max {sizes.max()} min "
          f"{sizes[sizes > 0].min()} rows, {int((sizes % 512 != 0).sum())} "
          f"of {experts} off the row tile", flush=True)

    def plain(lhs, rhs):
        return jnp.concatenate([
            lhs[e - n:e].astype(jnp.float32) @ rhs[g].astype(jnp.float32)
            for g, (e, n) in enumerate(zip(ends, sizes)) if n])

    for k, n in ((2048, 1024), (1024, 2048)):           # gate/up, down
        ks = jax.random.split(jax.random.PRNGKey(k), 3)
        lhs = jax.random.normal(ks[0], (rows, k), jnp.float32
                                ).astype(jnp.bfloat16)
        rhs = (0.02 * jax.random.normal(ks[1], (experts, k, n), jnp.float32)
               ).astype(jnp.bfloat16)
        ct = jax.random.normal(ks[2], (rows, n), jnp.float32
                               ).astype(jnp.bfloat16)
        gs = jnp.asarray(sizes, jnp.int32)

        def loss(fn, lhs, rhs):
            return (fn(lhs, rhs).astype(jnp.float32)
                    * ct.astype(jnp.float32)).sum()

        kern = lambda a, w: grouped_matmul(a, w, gs)    # noqa: E731
        out = jax.jit(kern)(lhs, rhs)
        d_lhs, d_rhs = jax.jit(jax.grad(lambda *a: loss(kern, *a),
                                        argnums=(0, 1)))(lhs, rhs)
        with jax.default_matmul_precision("highest"):
            out_r = jax.jit(plain)(lhs, rhs)
            d_lhs_r, d_rhs_r = jax.jit(jax.grad(lambda *a: loss(plain, *a),
                                                argnums=(0, 1)))(lhs, rhs)
        name = f"grouped_matmul ({rows},{k})x({experts},{k},{n})"
        _check_close(f"{name} fwd", out, out_r)
        _check_close(f"{name} d-rows", d_lhs, d_lhs_r)
        _check_close(f"{name} d-weights", d_rhs, d_rhs_r)
        empty = np.asarray(d_rhs, np.float32)[[5, 63]]
        assert not empty.any(), "an expert with no rows got a gradient"
    assert any(site == "grouped_matmul" and impl == "megablox" and count
               for site, impl, _, count in dispatch_report()), dispatch_report()


def kernel_adam8bit():
    """The one-pass int8 AdamW update (``ops/pallas/adam8bit_kernel.py``),
    Mosaic-compiled, at a leaf of each form it takes in the two train
    cells: rows (XL's c_fc), stored transposed (XL's mlp c_proj), a stack
    of experts, and OLMoE's untied head whose rows take 32-row blocks.
    bf16 gradient, moments live (second of two steps), against
    ``adam8bit._leaf_moments`` + decay + lr compiled by XLA, at the CPU
    test's tolerances (``tests/unit/test_adam8bit.py``)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.adam8bit import _leaf_moments
    from deepspeed_tpu.ops.pallas.adam8bit_kernel import (apply_leaf,
                                                          leaf_refusal)

    b1, b2, eps, wd, lr, gscale = 0.9, 0.999, 1e-8, 0.1, 1e-3, 0.37

    @jax.jit
    def chain(g, p, mc, rc, sc, c1, c2):
        upd, mc2, rc2, sc2 = _leaf_moments(
            g.astype(jnp.float32) * gscale, mc, rc, sc, b1=b1, b2=b2, c1=c1,
            c2=c2, eps=eps)
        return p - lr * (upd + wd * p), mc2, rc2, sc2

    # in place, as in the engine's step: the state is donated (undonated,
    # XLA copies the master first and, with the call pinned to HBM, its
    # memory-space assignment fails a check in libtpu 0.0.34)
    @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4))
    def kernel(g, p, mc, rc, sc, c1, c2):
        return apply_leaf(g, p, mc, rc, sc,
                          jnp.stack([jnp.float32(gscale), jnp.float32(lr),
                                     c1, c2]),
                          b1=b1, b2=b2, eps=eps, wd=wd, l2=0.0,
                          interpret=False)

    for shape in ((1600, 6400), (6400, 1600), (64, 2048, 1024),
                  (2048, 50304)):
        assert leaf_refusal(shape, jnp.float32) is None
        ks = jax.random.split(jax.random.PRNGKey(shape[-1]), 4)
        cols = jnp.exp(2.0 * jax.random.normal(ks[3], shape[-1:]))
        state = (jax.random.normal(ks[0], shape, jnp.float32),
                 jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.uint8),
                 {"m": jnp.ones(shape[:-1] + (1,), jnp.float32),
                  "r": jnp.ones(shape[:-1] + (1,), jnp.float32)})
        for t in (1, 2):
            g = (jax.random.normal(ks[t], shape, jnp.float32) * cols
                 ).astype(jnp.bfloat16)
            c = (jnp.float32(1 - b1 ** t), jnp.float32(1 - b2 ** t))
            want = chain(g, *state, *c)
            state = kernel(g, *state, *c)
        (p_k, mc_k, rc_k, sc_k), (p_w, mc_w, rc_w, sc_w) = state, want
        dp = float(jnp.max(jnp.abs(p_k - p_w) / jnp.maximum(jnp.abs(p_w),
                                                            1e-3)))
        flips = [(int(jnp.max(d)), float(jnp.mean(d > 0))) for d in (
            jnp.abs(a.astype(jnp.int32) - b.astype(jnp.int32))
            for a, b in ((mc_k, mc_w), (rc_k, rc_w)))]
        ds = max(float(jnp.max(jnp.abs(sc_k[k] / sc_w[k] - 1)))
                 for k in ("m", "r"))
        print(f"  adam8bit {shape}: master {dp:.2e}, m codes off by "
              f"{flips[0][0]} on {flips[0][1]:.2e}, r codes off by "
              f"{flips[1][0]} on {flips[1][1]:.2e}, scales {ds:.2e}",
              flush=True)
        assert np.isfinite(np.asarray(p_k)).all()
        assert dp <= 1e-6 and ds <= 1e-6, (shape, dp, ds)
        assert flips[0][0] <= 1 and flips[0][1] <= 1e-3, (shape, flips)
        assert flips[1][0] <= 1 and flips[1][1] <= 0.03, (shape, flips)
def _dispatch_case(T, M, I, R, E, first, k, tiles, rows_impl):
    """One expert layer (``E`` of ``R`` routed experts held from ``first``
    on, top-``k``) through the sorted dispatch against a dense float32
    loop over the held experts: output, d-tokens, the experts' gradients
    and the router's (where the d-weights of the combine end up)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.parallel.moe import MoEConfig, MoELayer

    share = E < R
    cfg = MoEConfig(num_experts=E, routed_experts=R if share else None,
                    first_expert=first if share else None, top_k=k,
                    drop_tokens=False, norm_topk_prob=True,
                    expert_act="swiglu")
    layer = MoELayer(cfg, model_dim=M, hidden_dim=I, dtype=jnp.bfloat16)
    ks = jax.random.split(jax.random.PRNGKey(30), 3)
    x = jax.random.normal(ks[0], (T, M), jnp.float32).astype(jnp.bfloat16)
    ct = jax.random.normal(ks[1], (T, M), jnp.float32)
    p = jax.tree_util.tree_map(
        lambda a: a.value if hasattr(a, "value") else a,
        layer.init(ks[2], x[:256])["params"],
        is_leaf=lambda a: hasattr(a, "value"))
    p = {"gate": {"wg": p["gate"]["wg"] * 30 * (512 / M) ** 0.5},
         "experts": {n: w * 3 * (512 / M) ** 0.5
                     for n, w in p["experts"].items()}}

    def plain(p, x):
        x = x.astype(jnp.float32)
        probs = jax.nn.softmax(x @ p["gate"]["wg"], -1)
        w, chosen = jax.lax.top_k(probs, k)
        w = w / w.sum(-1, keepdims=True)
        out = 0.0
        for e in range(E):
            gate, up, down = (p["experts"][n][e] for n in
                              ("gate", "up", "down"))
            mine = jnp.where(chosen == (first if share else 0) + e, w,
                             0.0).sum(-1)
            out += mine[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down)
        return out

    def loss(fn, p, x):
        return (fn(p, x).astype(jnp.float32) * ct).sum()

    kern = lambda p, x: layer.apply({"params": p}, x)[0]    # noqa: E731
    out = jax.jit(kern)(p, x)
    d_p, d_x = jax.jit(jax.grad(lambda *a: loss(kern, *a),
                                argnums=(0, 1)))(p, x)
    with jax.default_matmul_precision("highest"):
        out_r = jax.jit(plain)(p, x)
        d_p_r, d_x_r = jax.jit(jax.grad(lambda *a: loss(plain, *a),
                                        argnums=(0, 1)))(p, x)
    name = f"dispatch ({T},{M}) top-{k}, {E} of {R} experts held"
    _check_close(f"{name} fwd", out, out_r)
    _check_close(f"{name} d-tokens", d_x, d_x_r)
    for n in ("gate", "up", "down"):
        _check_close(f"{name} d-{n}", d_p["experts"][n], d_p_r["experts"][n])
    _check_close(f"{name} d-router", d_p["gate"]["wg"], d_p_r["gate"]["wg"])
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    report = dispatch_report()
    assert any(site == "grouped_matmul" and impl == "megablox"
               and tiles in reason for site, impl, reason, _ in report), report
    assert any(site == "moe_rows" and impl == rows_impl[0]
               and reason.startswith(rows_impl[1])
               for site, impl, reason, _ in report), report


def _skipped_rows(S, M, k, live_share):
    """What the chip leaves in the rows of a share's buffer that hold no
    pair: the row kernel writes zeros up to the end of the block of 1024
    that holds the last pair and nothing past it; the grouped matmul
    neither reads nor writes such rows, so there both hold whatever memory
    held (reported, not judged: nothing downstream reads it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.grouped_matmul import grouped_matmul, repeat_gather

    R = S * k
    live = int(R * live_share) // 512 * 512 - 512      # ends inside a block
    rng = np.random.default_rng(0)
    order = rng.permutation(R).astype(np.int32)
    order[live:] = R
    inv = np.full(R, R, np.int32)
    inv[order[:live]] = np.arange(live, dtype=np.int32)
    x = jax.random.normal(jax.random.PRNGKey(0), (S, M),
                          jnp.float32).astype(jnp.bfloat16)
    rows = jax.jit(lambda x, o, i: repeat_gather(x, o, i, True,
                                                 per_device=True))(
        x, jnp.asarray(order), jnp.asarray(inv))
    block_end = -(-live // 1024) * 1024
    if np.asarray(rows[live:block_end], np.float32).any():
        raise AssertionError(f"rows {live} to {block_end} are not zero")
    tail = np.asarray(rows[block_end:], np.float32)
    want = np.asarray(x, np.float32)[order[:live] // k]
    if not (np.asarray(rows[:live], np.float32) == want).all():
        raise AssertionError("gathered rows are not copies of their tokens")
    w = jnp.ones((16, M, 128), jnp.bfloat16)
    sizes = jnp.full((16,), live // 16, jnp.int32)
    y = np.asarray(jax.jit(lambda a, b, c: grouped_matmul(
        a, b, c, per_device=True))(rows, w, sizes)[live:], np.float32)
    def held(a):
        return (f"{np.count_nonzero(~np.isfinite(a))} non-finite and "
                f"{np.count_nonzero(a)} non-zero of {a.size} entries")

    print(f"  [rows past the groups] ({S} x {k}, {M}), {live} live: the "
          f"gather's unwritten blocks hold {held(tail)}; the grouped "
          f"matmul's output there holds {held(y)}", flush=True)


ROW_KERNEL_SHAPES = {         # tokens, top-k, row width, routed experts
    "mellum2": (32768, 8, 2304, 64),
    "lfm2": (32768, 4, 2048, 64),
}


def _traced_ops(out):
    """``[(op name, start ns, ns), ...]`` of the first device in the newest
    profiler trace under ``out``."""
    import glob

    from benchmark.trace_reduce import read_xplane

    path = max(glob.glob(os.path.join(out, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    dev_ops, _, _ = read_xplane(path)
    return next(iter(dev_ops.values()))


def _traced_op_times(out):
    """``{op name: [ns, ...]}`` of :func:`_traced_ops`."""
    times = {}
    for name, _, dur in _traced_ops(out):
        times.setdefault(name, []).append(dur)
    return times


def row_kernel_times(builds, reps=5, shapes=ROW_KERNEL_SHAPES):
    """Device time a call of each row kernel (``ops/pallas/moe_rows.py``:
    ``pack``, ``gather`` plain and scaled, ``combine`` and its d-weights)
    at the shapes of the cells that hold a share (experts 16-31 of the
    routed ones, a seeded even routing), under every module of ``builds``
    (name -> module: this tree's, a parent checkout's) in one process and
    one profiler trace, each custom call under a name of its own; every
    output equal, bit for bit, to the first build's over the rows a call
    writes.  Prints a line a (shape, build) with the ms a call and the
    share of the gather's row DMAs started inside a vector block, where
    the build counts it (``gather_starts``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chiprun_out", "row_kernel_times")
    runs, lines = [], {}
    for shape, (S, k, M, routed) in shapes.items():
        R = S * k
        rng = np.random.default_rng(46)
        chosen = np.argsort(rng.random((S, routed)), axis=1)[:, :k]
        flat = (chosen - 16).reshape(-1)
        flat = np.where((flat >= 0) & (flat < 16), flat, 16)
        order = np.argsort(flat, kind="stable").astype(np.int32)
        inv = np.argsort(order).astype(np.int32)
        n_live = int((flat < 16).sum())
        inv = jnp.asarray(np.where(flat < 16, inv, R).astype(np.int32))
        tokens = jnp.asarray(np.where(np.arange(R) < n_live, order // k,
                                      S).astype(np.int32))
        live = jnp.array([n_live], jnp.int32)
        ks = jax.random.split(jax.random.PRNGKey(46), 5)
        x, g = (jax.random.normal(kk, (S, M), jnp.float32).astype(
            jnp.bfloat16) for kk in ks[:2])
        y = jax.random.normal(ks[2], (R, M), jnp.float32).astype(jnp.bfloat16)
        w = jax.random.uniform(ks[3], (S, k), jnp.float32)
        scale = jax.random.uniform(ks[4], (R, 1), jnp.float32)
        written = -(-n_live // 1024) * 1024     # the gather's last block
        first = None
        for build, rows in builds.items():
            tag = f"{shape}_{build}"

            def named(kernel, tag=tag):
                return f"{tag}_{kernel}"

            calls = {
                "pack": (jax.jit(lambda x, n: rows.pack_rows(
                    x, n, name=named("pack"))),
                    (x, jnp.array([S], jnp.int32))),
                "pack_live": (jax.jit(lambda y, n: rows.pack_rows(
                    y, n, name=named("pack_live"))), (y, live)),
            }
            px = calls["pack"][0](*calls["pack"][1])
            py = calls["pack_live"][0](*calls["pack_live"][1])
            calls.update({
                "gather": (jax.jit(lambda p, i, n: rows.gather_rows(
                    p, i, n, name=named("gather"))), (px, tokens, live)),
                "gather_scaled": (jax.jit(
                    lambda p, i, n, s: rows.gather_rows(
                        p, i, n, s, name=named("gather_scaled"))),
                    (px, tokens, live, scale)),
                "combine": (jax.jit(lambda p, i, w: rows.combine_rows(
                    p, i, w, name=named("combine"))), (py, inv, w)),
                "combine_dw": (jax.jit(
                    lambda p, i, w, g: rows.combine_rows(
                        p, i, w, g, name=named("combine_dw"))),
                    (py, inv, w, g)),
            })
            got = {}
            for kernel, (fn, args) in calls.items():
                res = jax.block_until_ready(fn(*args))
                if kernel.startswith("gather"):
                    res = res[:written]
                elif kernel == "pack_live":
                    res = res[:-(-n_live // 256) * 256]
                got[kernel] = np.asarray(res.astype(jnp.float32)
                                         if res.dtype == jnp.bfloat16 else res)
                print(f"  [row kernels] {tag} {kernel} ran", flush=True)
            first = first or got
            for kernel in got:
                if not np.array_equal(got[kernel], first[kernel],
                                      equal_nan=True):
                    raise AssertionError(
                        f"{tag} {kernel}: not the first build's output, "
                        f"{np.count_nonzero(got[kernel] != first[kernel])} "
                        f"entries differ")
            want = np.asarray(x, np.float32)[np.asarray(tokens)[:n_live]]
            if not np.array_equal(got["gather"][:n_live], want):
                raise AssertionError(f"{tag} gather: not jnp.take's rows")
            line = lines[tag] = {"shape": shape, "build": build,
                                 "live_rows": n_live}
            if hasattr(rows, "gather_starts"):
                block, loop = rows.gather_starts(n_live, R)
                line["gather_block_share"] = round(
                    block / max(block + loop, 1), 4)
            runs.append((tag, calls))
    os.makedirs(out, exist_ok=True)
    with jax.profiler.trace(out):
        for _ in range(reps):           # builds interleaved within a rep
            for tag, calls in runs:
                for fn, args in calls.values():
                    jax.block_until_ready(fn(*args))
    times = _traced_op_times(out)
    for tag, calls in runs:
        for kernel in calls:
            durs = times.get(f"{tag}_{kernel}", ())
            if len(durs) != reps:       # say what the trace did hold
                lines[tag][f"{kernel}_events"] = len(durs)
            if durs:
                lines[tag][f"{kernel}_ms"] = round(
                    float(np.median(durs)) / 1e6, 4)
        print("  [row kernels] " + json.dumps(lines[tag]), flush=True)
    return lines


def _load_pallas_module(name, root, file):
    """``ops/pallas/<file>`` of the checkout at ``root`` under a name of its
    own (fresh jits; its relative imports resolve in this tree)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"deepspeed_tpu.ops.pallas._{file[:-3]}_{name}", os.path.join(
            root, "deepspeed_tpu", "ops", "pallas", file))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def kernel_share_dispatch(parent: str = ""):
    """A share of an expert layer through the sorted dispatch: the pairs
    held elsewhere lie behind the last group, in rows the Pallas grouped
    matmul neither reads nor writes, forward or backward.  Whatever the
    chip's memory holds there must reach no token and no gradient
    (``ragged_dot`` on the CPU writes zeros there, so only the chip can
    show it).  At a small shape (experts 4-7 of 16, top-4, rows of 512)
    and at the third cell's own (``train-mellum2-8k-1chip``: 32768 tokens
    x top-8, rows of 2304, experts 16-31 of 64), where the rows move
    through the Pallas row kernels (``ops/pallas/moe_rows.py``).  With
    ``parent``, the root of a parent checkout, then each row kernel alone,
    timed a call at Mellum 2's and the seventh cell's shapes under that
    checkout's module and this tree's (:func:`row_kernel_times`), every
    output held to the parent's bit for bit:
    ``python3 -c "import chip_smoke; chip_smoke.kernel_share_dispatch('.chip_archive/parent')"``."""
    _dispatch_case(2048, 512, 256, 16, 4, 4, 4, "(512, 512, 256)",
                   ("pallas", "rows 8192 x 512"))
    _dispatch_case(32768, 2304, 896, 64, 16, 16, 8, "(512, 768, 896)",
                   ("pallas", "rows 262144 x 2304"))
    _skipped_rows(32768, 2304, 8, 0.25)
    if parent:
        from deepspeed_tpu.ops.pallas import moe_rows

        root = os.path.dirname(os.path.abspath(__file__))
        row_kernel_times({
            "parent": _load_pallas_module(
                "parent", os.path.join(root, parent), "moe_rows.py"),
            "change": moe_rows})


def kernel_full_dispatch():
    """The twin with every expert held, at the second cell's shape
    (``train-olmoe-z3-1chip``: 8192 tokens x top-8 of 64, rows of 2048): a
    full permutation, which the guard leaves to XLA's gathers."""
    _dispatch_case(8192, 2048, 1024, 64, 64, 0, 8, "(512, 1024, 1024)",
                   ("xla", "every row holds a pair"))


def kernel_flash_window_gqa(B=4, S=8192, H=32, KV=4, D=128,
                            windows=(1024, 2048, None)):
    """The third cell's attention at the cell's own shape
    (``train-mellum2-8k-1chip``: 4 rows of 8192 tokens, 32 query heads on
    4 key-value heads of head_dim 128), with the 1024-key window, the
    fourth cell's 2048 and without, forward and backward against a float32
    reference computed in query blocks (the whole score matrix would be
    34 GB a pass).  Four rows
    and four key-value heads, because the sum of dk, dv over a group lives
    on the chip's write-back of an output block whose index stays put until
    the group's last program and then moves on, to the next key-value head
    and to the next row: one row of one key-value head never moves it, and
    interpret mode stores every grid step.

    ``kernel_flash_lanes_256`` runs the same comparison at the eighth
    cell's shape (``train-qwen3next-gdn-8k-1chip``: 16 query heads on 2
    key-value heads of head_dim 256, one head a 256-lane block, no
    window)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    QB = 256
    G = H // KV
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q, ct = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
             .astype(jnp.bfloat16) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (B, S, KV, D), jnp.float32)
            .astype(jnp.bfloat16) for kk in ks[2:])

    def ref(q, k, v, window):
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))

        @jax.checkpoint
        def block(args):
            q_blk, i0 = args                                # (B, QB, H, D)
            q_blk = q_blk.reshape(B, QB, KV, G, D)          # head h: h // G
            s = jnp.einsum("bqngd,btnd->bngqt", q_blk, k) * D ** -0.5
            back = (i0 + jnp.arange(QB))[:, None] - jnp.arange(S)[None, :]
            keep = back >= 0
            if window is not None:
                keep &= back < window
            p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
            return jnp.einsum("bngqt,btnd->bqngd", p, v).reshape(B, QB, H, D)

        blocks = q.reshape(B, S // QB, QB, H, D).transpose(1, 0, 2, 3, 4)
        out = jax.lax.map(block, (blocks, jnp.arange(0, S, QB)))
        return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)

    def loss(fn, q, k, v):
        return (fn(q, k, v).astype(jnp.float32)
                * ct.astype(jnp.float32)).sum()

    # 1024 and 2048 (the fourth cell's): straight-line sweeps of three and
    # five tiles a program, the first query tiles' and the last key tiles'
    # missing ones computed void (PR 43); None: the loop over pairs
    for window in windows:
        flash = lambda q, k, v: flash_attention(q, k, v, window=window)  # noqa: E731
        plain = lambda q, k, v: ref(q, k, v, window)                     # noqa: E731
        out = jax.jit(flash)(q, k, v)
        grads = jax.jit(jax.grad(lambda *a: loss(flash, *a),
                                 argnums=(0, 1, 2)))(q, k, v)
        with jax.default_matmul_precision("highest"):
            out_r = jax.jit(plain)(q, k, v)
            grads_r = jax.jit(jax.grad(lambda *a: loss(plain, *a),
                                       argnums=(0, 1, 2)))(q, k, v)
        name = f"flash ({B},{S},{H}/{KV},{D}) window {window}"
        _check_close(f"{name} fwd", out, out_r)
        for n, g, gr in zip(("dq", "dk", "dv"), grads, grads_r):
            _check_close(f"{name} {n}", g, gr)
            if n == "dq":
                continue
            # every row and every key-value head on its own: one whose sum
            # was written early, or over another's, is small in the whole
            g, gr = (np.asarray(t, np.float32) for t in (g, gr))
            worst = (np.abs(g - gr).max(axis=(1, 3))
                     / np.abs(gr).max(axis=(1, 3)))               # (B, KV)
            print(f"  {name} {n}: worst of {B} rows x {KV} key-value heads "
                  f"{worst.max():.2e}", flush=True)
            assert worst.max() <= TOL, (name, n, worst)

        _diff_from_the_parents(
            name, ("fwd", "dq", "dk", "dv"), (out, *grads),
            lambda fa: _out_and_grads(lambda q, k, v: fa.flash_attention(
                q, k, v, window=window), loss, (q, k, v)))


def kernel_flash_lanes_256():
    """:func:`kernel_flash_window_gqa` at (4, 8192, 16 / 2, 256), no window."""
    kernel_flash_window_gqa(H=16, KV=2, D=256, windows=(None,))


def kernel_gated_delta_wide(time_it: bool = True):
    """:func:`kernel_gated_delta` at the ninth cell's shape (PR 52): ``(2,
    8192)`` rows, 30 key heads of 96 and 30 value heads of 192 channels
    (states of 96 x 192, read by the kernels in lane slots of 128 x 256:
    since PR 55 the operands arrive in them, ``slots=(96, 192)``, and the
    timing holds no pad or cut), ``beta = 2 sigmoid(.)`` in (0, 2), against
    ``benchmark/reference/olmo_hybrid.py``'s recurrence."""
    kernel_gated_delta(time_it, wide=True)


def kernel_gated_delta(time_it: bool = True, wide: bool = False):
    """The gated delta rule at the eighth cell's shape, ``(3, 8192)`` rows,
    16 key heads and 32 value heads of 128 channels, chunk 64: the fused
    kernels (PR 49: what ``auto`` takes on the chip) and the XLA form,
    forward and ``jax.vjp`` (five cotangents) in bf16, against
    ``benchmark/reference/qwen3next.py``'s recurrence one position a step
    in float32, every row of the batch held apart; decays drawn so that
    states outlive chunks.  The kernels' path is timed a layer, forward and
    forward + backward, beside the least the recurrence's own traffic allows
    (``benchmark/flops_qwen3next.py``), and its custom calls a row from a
    profiler trace (``gated_delta_fwd`` runs once a row forward and once
    more, for the states, in the backward)."""
    import functools
    import tempfile
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness.manifest import ROOT, load_module
    from deepspeed_tpu.ops.gated_delta import gated_delta_rule

    reference = load_module(ROOT, "reference",
                            "olmo_hybrid" if wide else "qwen3next")
    B, S, Hk, Hv, dk, d = (2, 8192, 30, 30, 96, 192) if wide \
        else (3, 8192, 16, 32, 128, 128)
    ks = jax.random.split(jax.random.PRNGKey(48), 6)

    def unit(key, H, scale):
        x = jax.random.normal(key, (B, S, H, dk), jnp.float32)
        x = x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6) * scale
        return x.reshape(B, S, H * dk).astype(jnp.bfloat16)

    q, k = unit(ks[0], Hk, dk ** -0.5), unit(ks[1], Hk, 1.0)
    v, do = (jax.random.normal(kk, (B, S, Hv * d), jnp.float32).astype(
        jnp.bfloat16) for kk in ks[2:4])
    # exp(g) between 0.5 and 0.999 a token: memories of 2 to 1000 tokens
    g = jnp.log(jax.random.uniform(ks[4], (B, S, Hv), jnp.float32, 0.5, 0.999))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, S, Hv), jnp.float32))
    if wide:        # linear_allow_neg_eigval: in (0, 2), a third above 1.5
        beta = 2.0 * jax.nn.sigmoid(
            2.0 * jax.random.normal(ks[5], (B, S, Hv), jnp.float32) + 0.3)
        print(f"  gated_delta: {100 * float((beta > 1.5).mean()):.1f}% of "
              f"beta above 1.5, largest {float(beta.max()):.4f}", flush=True)

    def ref(q, k, v, g, beta):
        f = reference._f32
        qh, kh = (jnp.repeat(f(t).reshape(B, S, Hk, dk), Hv // Hk, axis=2)
                  for t in (q, k))
        return reference.delta_rule(qh, kh, f(v).reshape(B, S, Hv, d), g,
                                    beta).reshape(B, S, Hv * d)

    def both(fn, do=do):
        def run(*args):
            out, vjp = jax.vjp(fn, *args)
            return (out,) + vjp(do.astype(out.dtype))
        return jax.jit(run)

    plain = (q, k, v, g, beta)
    want = both(ref)(*plain)
    for impl in ("pallas", "xla"):
        rule = functools.partial(gated_delta_rule, chunk=64, impl=impl,
                                 key_heads=Hk)
        args, run = plain, both(rule)
        if wide and impl == "pallas":
            # as the layer hands them over since PR 55: a head a lane slot,
            # o and the three cotangents come back so
            from deepspeed_tpu.ops.gated_delta import _dispatch
            from deepspeed_tpu.ops.pallas.gated_delta import _slots, _unslots

            rule = lambda *a: _dispatch(*a, 64, Hk, impl, False,  # noqa: E731
                                        slots=(dk, d))
            args = (_slots(q, Hk, dk), _slots(k, Hk, dk), _slots(v, Hv, d),
                    g, beta)
            run = both(rule, _slots(do, Hv, d))
        got = jax.block_until_ready(run(*args))
        if args is not plain:
            got = tuple(_unslots(t, H, w) for t, H, w in zip(
                got, (Hv, Hk, Hk, Hv), (d, dk, dk, d))) + got[4:]
        for n, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
            a, b = (np.asarray(t, np.float32) for t in (a, b))
            rows = [float(np.linalg.norm(a[r] - b[r]) / np.linalg.norm(b[r]))
                    for r in range(B)]
            print(f"  gated_delta {impl} {n}: |chunked - recurrence| / "
                  f"|recurrence| worst of {B} rows {max(rows):.2e}",
                  flush=True)
            assert np.isfinite(a).all() and max(rows) <= TOL, (n, rows)
        if not time_it:
            continue
        from benchmark import flops_olmo_hybrid as fl   # the general count

        conf = {"linear_num_key_heads": Hk, "linear_num_value_heads": Hv,
                "linear_key_head_dim": dk, "linear_value_head_dim": d,
                "layer_types": ["linear_attention"], "num_hidden_layers": 1}
        least = fl.gated_delta_bytes_per_step(conf, B * S) / 819e9 * 1e3
        for name, fn in (("forward", jax.jit(rule)), ("forward + backward",
                                                      run)):
            jax.block_until_ready(fn(*args))
            t0 = time.perf_counter()
            for _ in range(5):
                out = fn(*args)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / 5 * 1e3
            print(f"  gated_delta {impl}: {name} {ms:.3f} ms a layer of {B} "
                  f"rows (least for the recurrence's traffic forward + "
                  f"backward at 819 GB/s: {least:.3f} ms)", flush=True)
        if impl == "pallas":
            out = tempfile.mkdtemp(prefix="gated_delta_trace_")
            with jax.profiler.trace(out):
                jax.block_until_ready(run(*args))
            for op, ns in sorted(_traced_op_times(out).items()):
                if "gated_delta" in op:
                    print(f"  gated_delta pallas: {op} {len(ns)} calls, "
                          f"{np.mean(ns) / 1e6:.3f} ms a row", flush=True)


def kernel_gated_delta_channels(time_it: bool = True):
    """The delta rule under a decay a KEY CHANNEL (Kimi Delta Attention; the
    eleventh cell's 32 heads of 128 x 128, chunk 64, log-decays in (-5, 0)
    with a quarter of the channels pinned at the bound over a chunk), ``(1,
    2048)`` against ``benchmark/reference/ling3.py kda_rule``'s recurrence
    in float32, forward and ``jax.vjp``: the channel kernels (PR 60: what
    ``auto`` takes on the chip) and the XLA form; then both timed at the
    cell's ``(1, 8192)``."""
    import functools
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness.manifest import ROOT, load_module
    from deepspeed_tpu.ops.gated_delta import gated_delta_rule

    reference = load_module(ROOT, "reference", "ling3")
    B, H, d = 1, 32, 128

    def operands(S):
        ks = jax.random.split(jax.random.PRNGKey(60), 6)

        def unit(key, scale):
            x = jax.random.normal(key, (B, S, H, d), jnp.float32)
            x = x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)
            return (x * scale).reshape(B, S, H * d).astype(jnp.bfloat16)

        v, do = (jax.random.normal(kk, (B, S, H * d), jnp.float32).astype(
            jnp.bfloat16) for kk in ks[2:4])
        g = -5.0 * jax.nn.sigmoid(
            2.0 * jax.random.normal(ks[4], (B, S, H, d), jnp.float32))
        g = g.at[:, 64:128, :, :d // 4].set(-5.0)
        beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, S, H),
                                                jnp.float32))
        return (unit(ks[0], d ** -0.5), unit(ks[1], 1.0), v, g, beta), do

    def both(fn, do):
        def run(*args):
            out, vjp = jax.vjp(fn, *args)
            return (out,) + vjp(do.astype(out.dtype))
        return jax.jit(run)

    def ref(q, k, v, g, beta):
        f = reference._f32
        S = q.shape[1]
        return reference.kda_rule(
            *(f(t).reshape(B, S, H, d) for t in (q, k, v)), g,
            beta).reshape(B, S, H * d)

    args, do = operands(2048)
    want = both(ref, do)(*args)
    for impl in ("pallas", "xla"):
        rule = functools.partial(gated_delta_rule, chunk=64, impl=impl)
        got = jax.block_until_ready(both(rule, do)(*args))
        for n, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
            a, b = (np.asarray(t, np.float32) for t in (a, b))
            err = float(np.linalg.norm(a - b) / np.linalg.norm(b))
            print(f"  gated_delta channels {impl} {n}: |chunked - "
                  f"recurrence| / |recurrence| {err:.2e}", flush=True)
            # the XLA form rounds the decays' cotangents (PERF.md, PR 58)
            assert np.isfinite(a).all() and err <= (
                TOL if impl == "pallas" or n != "dg" else 0.1), (n, err)
    if not time_it:
        return
    args, do = operands(8192)
    for impl in ("pallas", "xla"):
        rule = functools.partial(gated_delta_rule, chunk=64, impl=impl)
        for name, fn in (("forward", jax.jit(rule)),
                         ("forward + backward", both(rule, do))):
            jax.block_until_ready(fn(*args))
            t0 = time.perf_counter()
            for _ in range(5):
                out = fn(*args)
            jax.block_until_ready(out)
            print(f"  gated_delta channels {impl}: {name} "
                  f"{(time.perf_counter() - t0) / 5 * 1e3:.3f} ms a "
                  f"layer-row of 8192", flush=True)


def kernel_gated_norm(time_it: bool = True):
    """A Gated DeltaNet layer's gated output norm on the rows (PR 53;
    ``ops/pallas/qk_rows.py gated_norm_rows`` behind ``ops/gated_delta.py
    gated_norm_plan``) at the eighth cell's shape, ``(3, 8192, 32 heads of
    128)``: ``y = rms_norm(o, w, eps) * silu(z)``, forward and ``jax.vjp``
    (``do``, ``dz``, ``dw``), beside the model's own lines on the ``(B, S,
    H, d)`` float32 view, both against the same arithmetic in float64 on
    the host, every row of the batch held apart.  In bf16 (what the cell
    runs) the result's one rounding decides; in float32 the kernel's
    sigmoid (the approximate reciprocal and one Newton step) stands against
    XLA's division.  Timed in bf16: forward + backward against the least
    the HBM rate allows for the eight vectors the two passes move, and the
    two custom calls one by one from a profiler trace."""
    import tempfile
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.common import rms_norm
    from deepspeed_tpu.ops import gated_delta

    B, S, H, d, eps = 3, 8192, 32, 128, 1e-6
    ks = jax.random.split(jax.random.PRNGKey(53), 4)
    w = 1 + 0.2 * jax.random.normal(ks[3], (d,), jnp.float32)
    plan = gated_delta.gated_norm_plan(
        jax.ShapeDtypeStruct((B, S, H * d), jnp.bfloat16), d)
    assert plan == ("direct", None), plan

    def rows(o, z, w):
        return gated_delta.gated_norm_rows(o, z, w, d, plan, eps=eps)

    def view(o, z, w):
        y = rms_norm(o.reshape(B, S, H, d).astype(jnp.float32), w, eps)
        return (y * jax.nn.silu(z.reshape(B, S, H, d).astype(jnp.float32))
                ).astype(o.dtype).reshape(o.shape)

    def float64(o, z, w, dy):
        """``(y, do, dz, dw)`` of one row of the batch."""
        o, z, dy = (np.asarray(t, np.float64).reshape(S, H, d)
                    for t in (o, z, dy))
        w = np.asarray(w, np.float64)
        inv = 1.0 / np.sqrt((o * o).mean(-1, keepdims=True) + eps)
        unit, s = o * inv, 1.0 / (1.0 + np.exp(-z))
        g = dy * z * s
        dz = dy * unit * w * s * (1.0 + z * (1.0 - s))
        gw = g * w
        do = inv * (gw - unit * (gw * unit).mean(-1, keepdims=True))
        return [t.reshape(S, H * d) for t in (unit * w * z * s, do, dz)] \
            + [(g * unit).sum((0, 1))]

    for dtype in (jnp.bfloat16, jnp.float32):
        o, z, dy = (jax.random.normal(kk, (B, S, H * d), jnp.float32)
                    .astype(dtype) for kk in ks[:3])
        z = (2 * z.astype(jnp.float32)).astype(dtype)   # gates out to +-8
        want = [float64(o[r], z[r], w, dy[r]) for r in range(B)]

        def both(fn):
            def run(o, z, w):
                out, vjp = jax.vjp(fn, o, z, w)
                return (out,) + vjp(dy)
            return jax.jit(run)

        for impl, fn in (("pallas", rows), ("view", view)):
            run = both(fn)
            got = jax.block_until_ready(run(o, z, w))
            for i, n in enumerate(("y", "do", "dz")):
                g = np.asarray(got[i], np.float64)
                err = [np.abs(g[r] - want[r][i]) for r in range(B)]
                size = [np.abs(want[r][i]).max() for r in range(B)]
                worst = max(e.max() / m for e, m in zip(err, size))
                mean = max(e.mean() / m for e, m in zip(err, size))
                print(f"  gated_norm {dtype.__name__} {impl} {n}: |. - "
                      f"float64| / max worst of {B} rows {worst:.2e}, mean "
                      f"{mean:.2e}", flush=True)
                assert np.isfinite(g).all() and worst <= TOL, (impl, n, worst)
            _check_close(f"gated_norm {dtype.__name__} {impl} dw", got[3],
                         sum(want[r][3] for r in range(B)))
            if not time_it or dtype != jnp.bfloat16:
                continue
            t0 = time.perf_counter()
            for _ in range(20):
                out = run(o, z, w)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / 20 * 1e3
            print(f"  gated_norm {impl}: forward + backward {ms:.3f} ms "
                  f"(least for 8 vectors of bf16 at 819 GB/s: "
                  f"{8 * B * S * H * d * 2 / 819e9 * 1e3:.3f} ms)", flush=True)
            if impl == "pallas":
                out = tempfile.mkdtemp(prefix="gated_norm_trace_")
                with jax.profiler.trace(out):
                    for _ in range(5):
                        jax.block_until_ready(run(o, z, w))
                for call, ns in sorted(_traced_op_times(out).items()):
                    if "gated_norm" in call:
                        print(f"  gated_norm pallas: {call} {len(ns)} calls, "
                              f"{np.mean(ns) / 1e6:.3f} ms a call",
                              flush=True)
        del want, got


def kernel_mhc_rows(time_it: bool = True, variants=(),
                    shape=(1, 8192, 4, 3584)):
    """The hyper-connections' four row kernels (PR 65,
    ``ops/pallas/mhc_rows.py`` behind ``ops/hyper_connection.py read`` /
    ``write``) at the twelfth cell's shape, one packed row of 8,192 tokens x
    4 lanes of 3,584 channels in bf16, under gains and biases away from
    their near-identity start: ``u``, the three maps, ``X'`` and the
    gradients of a seeded scalar in ``X``, ``y``, ``phi``, the gains and the
    biases, the kernels and the ``jax.numpy`` form in bf16 each against the
    ``jax.numpy`` form on the same values in float32.  Timed from profiler
    traces: the read pass and the write-back pass, forward and (forward +
    backward) - forward, each form's whole device time, and the four custom
    calls one by one beside the bytes each must move at 819 GB/s.
    The passes are each timed forward and as the ``jax.vjp`` alone, which
    holds what of the forward the backward needs (of the kernels' read pass
    ``mhc_read``; the write-back's needs nothing).  ``variants``: settings of
    the module's constants (``{"GROUP": 8, "TILE": 256}``) to time beside
    the ones it has; ``shape``: ``(B, S, lanes, E)``."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops import hyper_connection as mhc
    from deepspeed_tpu.ops.pallas import mhc_rows
    from deepspeed_tpu.telemetry.device_scopes import _self_times

    B, S, n, E = shape
    k = mhc_rows.numbers(n)
    kw = dict(n=n, iters=20, eps=1e-6, clamp=(-10.0, 10.0), rms_eps=1e-6)
    ks = jax.random.split(jax.random.PRNGKey(65), 8)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    x = normal(ks[0], B, S, n * E).astype(jnp.bfloat16)
    y = normal(ks[1], B, S, E).astype(jnp.bfloat16)
    phi = 0.02 * normal(ks[2], n * E, k)
    gains = tuple(jnp.full((1,), a, jnp.float32) for a in (0.7, 0.5, 0.9))
    biases = (normal(ks[3], n), normal(ks[4], n),
              normal(ks[5], n, n) + 3.0 * jnp.eye(n))
    target = normal(ks[6], B, S, n * E).astype(jnp.bfloat16)
    plan = mhc._plan(x, n)
    assert plan == ("direct", None), plan
    spec = mhc._Spec(**kw, plan=plan, interpret=False)

    def plain_read(x, phi, gains, biases):
        made = mhc.maps(x, phi, gains, biases, **kw)
        return mhc.pre(x, made.pre), tuple(made[:3])

    def plain_write(x, y, res, post):
        return mhc.post(x, y, res, post)

    def kernel_read(x, phi, gains, biases):
        return mhc._read(x, phi, gains, biases, spec)[:2]

    def kernel_write(x, y, res, post):
        return mhc._write(x, y, res, post, spec)

    def whole(public, x, y, phi, gains, biases, target):
        """Both passes around ``F(u) = y + u`` as a model calls them."""
        if public:
            u, made = mhc.read(x, phi, gains, biases, **kw)
            out = mhc.write(x, (y + u).astype(x.dtype), made)
        else:
            u, made = plain_read(x, phi, gains, biases)
            out = plain_write(x, (y + u).astype(x.dtype), *made[1:][::-1])
        loss = (out.astype(jnp.float32) * target.astype(jnp.float32)).sum()
        return loss, (u, *made[:3], out)

    def run(public, x, y):
        return jax.jit(jax.value_and_grad(
            lambda *a: whole(public, *a), argnums=(0, 1, 2, 3, 4),
            has_aux=True))(x, y, phi, gains, biases, target)

    names = ("u", "H_pre", "H_post", "H_res", "X'", "dX", "dy", "dphi",
             "da_pre", "da_post", "da_res", "db_pre", "db_post", "db_res")

    def parts(result):
        (_, values), grads = result
        return [np.asarray(t, np.float64) for t in
                (*values, *jax.tree_util.tree_leaves(grads))]

    want = parts(run(False, x.astype(jnp.float32), y.astype(jnp.float32)))
    errs = {}
    for form, public in (("jax.numpy", False), ("kernels", True)):
        got = parts(jax.block_until_ready(run(public, x, y)))
        assert len(got) == len(names) == len(want)
        assert all(np.isfinite(g).all() for g in got), form
        errs[form] = [np.linalg.norm(g - w) / np.linalg.norm(w)
                      for g, w in zip(got, want)]
        print(f"  mhc_rows {form} bf16 |. - float32| / |float32|: "
              + " ".join(f"{nm} {e:.1e}" for nm, e in zip(names, errs[form])),
              flush=True)
    # a gain's gradient is one number, a sum that cancels: the kernels are
    # held to what the same form in bf16 reads there
    for nm, mine, plain in zip(names, errs["kernels"], errs["jax.numpy"]):
        assert mine <= max(TOL, 3 * plain), (nm, mine, plain)
    del want, got
    if not time_it:
        return

    def device_ms(fn, args, reps=5):
        """``(device ms a call, {custom call: ms a call})`` of jitted
        ``fn`` from a profiler trace."""
        jax.block_until_ready(fn(*args))
        out = tempfile.mkdtemp(prefix="mhc_rows_trace_")
        with jax.profiler.trace(out):
            for _ in range(reps):
                jax.block_until_ready(fn(*args))
        calls = {c: np.mean(ns) / 1e6
                 for c, ns in _traced_op_times(out).items()
                 if c.startswith("mhc_")}
        # a while loop's event spans its body's: self times
        busy = sum(ns for _, _, ns in _self_times(_traced_ops(out)))
        return busy / reps / 1e6, calls

    made = jax.block_until_ready(jax.jit(plain_read)(x, phi, gains, biases))
    u, (_, post, res) = made
    cot = jax.tree_util.tree_map(
        lambda t: normal(ks[7], *t.shape).astype(t.dtype), made)
    moved = {"mhc_read": n + 1, "mhc_post": 2 * n + 1,
             "mhc_post_back": 2 * n + 2, "mhc_read_back": 3 * n + 1}

    def back(fn):
        """``fn``'s ``jax.vjp`` alone; the cotangent is the last argument (a
        closed-over one would be baked into the executable, 235 MB)."""
        def run(*args):
            out, vjp = jax.vjp(fn, *args[:-1])
            grads = vjp(args[-1])
            # the kernels hand dX' on as it came: no copy of it is timed
            return grads if isinstance(out, tuple) else grads[1:]
        return run

    def timings(label):
        table = {}
        for form, read, write in (("kernels", kernel_read, kernel_write),
                                  ("jax.numpy", plain_read, plain_write)):
            if label and form != "kernels":
                continue
            for piece, fn, args, ct in (
                    ("read", read, (x, phi, gains, biases), cot),
                    ("write", write, (x, y, res, post), target)):
                fwd, calls = device_ms(jax.jit(fn), args)
                vjp, more = device_ms(jax.jit(back(fn)), (*args, ct))
                table[form, piece] = (fwd, vjp)
                for call, ms in sorted({**calls, **more}.items()):
                    least = moved[call] * B * S * E * 2 / 819e9 * 1e3
                    print(f"  mhc_rows{label} {call}: {ms:.3f} ms a call, "
                          f"{100 * least / ms:.1f}% of 819 GB/s over the "
                          f"{moved[call]} E it must move ({least:.3f} ms)",
                          flush=True)
        for (form, piece), (fwd, vjp) in table.items():
            print(f"  mhc_rows{label} {form} {piece} pass: forward "
                  f"{fwd:.3f} ms, its vjp alone {vjp:.3f} ms of device time",
                  flush=True)

    timings("")
    for variant in variants:
        had = {name: getattr(mhc_rows, name) for name in variant}
        for name, value in variant.items():
            setattr(mhc_rows, name, value)
        jax.clear_caches()
        timings(" " + " ".join(f"{k}={v}" for k, v in variant.items()))
        for name, value in had.items():
            setattr(mhc_rows, name, value)
    jax.clear_caches()


def kernel_head_slots(time_it: bool = True):
    """Olmo-Hybrid's heads in lane slots from the filter to ``out_proj``
    (PR 55) at the ninth cell's shape, ``(2, 8192)`` rows, 30 key heads of
    96 and 30 value heads of 192 channels.  ``slot_rows``: the filter's
    11,520 lanes ``[q | k | v]`` to ``q / |q| 96^-1/2``, ``k / |k|`` (3,840
    lanes: 30 slots of 128) and ``v`` (7,680: 30 of 256), and the rows'
    cotangent from three cotangents that are random behind the heads too.
    ``gated_norm_rows``: ``rms_norm(o, w) * silu(z)`` with ``o`` read from
    its slots, ``z`` and ``y`` rows of 5,760 lanes.  Each against the same
    arithmetic in float64 on the host, a row of the batch at a time; the
    lanes behind a head read exactly zero; the four custom calls timed one
    by one from a profiler trace, beside the least the HBM rate allows for
    the lanes each moves."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops import gated_delta
    from deepspeed_tpu.ops.pallas.gated_delta import _slots

    B, S, H, dk, dv, eps = 2, 8192, 30, 96, 192, 1e-6
    sk, sv, width = 128, 256, 2 * H * dk + H * dv
    ks = jax.random.split(jax.random.PRNGKey(55), 8)
    bf = lambda key, n, scale=1.0: (scale * jax.random.normal(
        key, (B, S, n), jnp.float32)).astype(jnp.bfloat16)
    x, dq, dkey, dval = (bf(ks[0], width), bf(ks[1], H * sk),
                         bf(ks[2], H * sk), bf(ks[3], H * sv))
    plan = gated_delta.slots_plan(x, H, dk, H, dv, 64)
    assert plan == ("direct", None), plan

    @jax.jit
    def slots(x):
        out, vjp = jax.vjp(
            lambda x: gated_delta.slot_rows(x, H, dk, H, dv, plan), x)
        return (*out, *vjp((dq, dkey, dval)))

    def live(t, d):         # a row's slots (S, H * slot) -> (S, H, d)
        t = np.asarray(t, np.float64).reshape(S, H, -1)
        return t[..., :d], t[..., d:]

    got = jax.block_until_ready(slots(x))
    for r in range(B):
        xr = np.asarray(x[r], np.float64)
        want_dx = []
        for n, (lo, c) in enumerate(((0, dk ** -0.5), (H * dk, 1.0))):
            head = xr[:, lo:lo + H * dk].reshape(S, H, dk)
            inv = 1.0 / np.sqrt((head * head).sum(-1, keepdims=True) + eps)
            out, behind = live(got[n][r], dk)
            g, _ = live((dq, dkey)[n][r], dk)
            unit = head * inv
            want_dx.append((c * inv * (g - unit * (g * unit).sum(
                -1, keepdims=True))).reshape(S, H * dk))
            err = np.abs(out - c * unit).max() / np.abs(c * unit).max()
            print(f"  head_slots {'qk'[n]} row {r}: |. - float64| / max "
                  f"{err:.2e}", flush=True)
            assert err <= TOL and (behind == 0).all(), (n, r, err)
        out, behind = live(got[2][r], dv)
        assert (out.reshape(S, H * dv) == xr[:, 2 * H * dk:]).all() \
            and (behind == 0).all(), r
        want_dx.append(live(dval[r], dv)[0].reshape(S, H * dv))
        _check_close(f"head_slots dx row {r}", got[3][r],
                     np.concatenate(want_dx, axis=1))

    o, z, dy = bf(ks[4], H * dv), bf(ks[5], H * dv, 2.0), bf(ks[6], H * dv)
    w = 1 + 0.2 * jax.random.normal(ks[7], (dv,), jnp.float32)
    o_slots = _slots(o, H, dv)

    @jax.jit
    def norm(o_slots, z, w):
        out, vjp = jax.vjp(lambda *a: gated_delta.gated_norm_rows(
            *a, dv, plan, eps=eps), o_slots, z, w)
        return (out,) + vjp(dy)

    got_n = jax.block_until_ready(norm(o_slots, z, w))
    dw = 0.0
    for r in range(B):
        o_, z_, dy_ = (np.asarray(t[r], np.float64).reshape(S, H, dv)
                       for t in (o, z, dy))
        w_ = np.asarray(w, np.float64)
        inv = 1.0 / np.sqrt((o_ * o_).mean(-1, keepdims=True) + eps)
        unit, sg = o_ * inv, 1.0 / (1.0 + np.exp(-z_))
        g = dy_ * z_ * sg
        gw = g * w_
        do, behind = live(got_n[1][r], dv)
        assert (behind == 0).all(), r
        for n, a, b in (
                ("y", got_n[0][r], unit * w_ * z_ * sg),
                ("do", do, inv * (gw - unit * (gw * unit).mean(
                    -1, keepdims=True))),
                ("dz", got_n[2][r], dy_ * unit * w_ * sg * (
                    1.0 + z_ * (1.0 - sg)))):
            _check_close(f"head_slots gated_norm {n} row {r}",
                         np.asarray(a, np.float64).reshape(S, H * dv),
                         b.reshape(S, H * dv))
        dw = dw + (g * unit).sum((0, 1))
    _check_close("head_slots gated_norm dw", got_n[3], dw)
    if not time_it:
        return
    out = tempfile.mkdtemp(prefix="head_slots_trace_")
    with jax.profiler.trace(out):
        for _ in range(5):
            jax.block_until_ready((slots(x), norm(o_slots, z, w)))
    lanes = {"slot_rows": width + 2 * H * sk + H * sv,
             "slot_rows_back": 2 * width + 2 * H * sk + H * sv,
             "gated_norm_rows": H * sv + 2 * H * dv,
             "gated_norm_rows_back": 2 * H * sv + 3 * H * dv}
    for call, ns in sorted(_traced_op_times(out).items()):
        name = call.split(".")[0].lstrip("%")
        if name in lanes:
            least = B * S * lanes[name] * 2 / 819e9 * 1e3
            ms = np.mean(ns) / 1e6
            print(f"  head_slots: {call} {len(ns)} calls, {ms:.3f} ms a call "
                  f"(least for its {lanes[name]} lanes of bf16 at 819 GB/s: "
                  f"{least:.3f} ms, {100 * least / ms:.0f}% of the HBM rate)",
                  flush=True)


def kernel_flash_two_products():
    """The fifth cell's attention at the cell's own shape
    (``train-joyai-flash-8k-1chip``: 2 rows of 8192 tokens, 32 heads of 128
    nope + 64 rope channels, ONE rope key for all heads, values 128 wide):
    the flash kernels under a score of two products (``flash_attention(...,
    q_rope=, k_rope=)``), forward and all five gradients, against a float32
    reference computed in query blocks.  Two rows, because the
    sum of ``dk_rope`` over the 32 heads lives on the chip's write-back of
    an output block whose index stays put until the last head's programs
    and then moves on to the next row: one row never moves it, and
    interpret mode stores every grid step.  Each row of ``dk_rope`` and
    each (row, head) of ``dq_rope`` (two heads share a lane block) is also
    checked on its own."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    B, S, H, D, R, QB = 2, 8192, 32, 128, 64, 256
    ks = jax.random.split(jax.random.PRNGKey(38), 6)
    shapes = ((B, S, H, D), (B, S, H, R), (B, S, H, D), (B, S, 1, R),
              (B, S, H, D), (B, S, H, D))
    *ops, ct = (jax.random.normal(kk, s, jnp.float32).astype(jnp.bfloat16)
                for kk, s in zip(ks, shapes))

    def ref(qn, qr, kn, kr, v):
        qn, qr, kn, kr, v = (t.astype(jnp.float32)
                             for t in (qn, qr, kn, kr, v))

        @jax.checkpoint
        def block(args):
            qn_blk, qr_blk, i0 = args                       # (B, QB, H, .)
            s = (jnp.einsum("bqhd,bthd->bhqt", qn_blk, kn)
                 + jnp.einsum("bqhr,btr->bhqt", qr_blk, kr[:, :, 0])
                 ) * (D + R) ** -0.5
            keep = (i0 + jnp.arange(QB))[:, None] >= jnp.arange(S)[None, :]
            p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
            return jnp.einsum("bhqt,bthd->bqhd", p, v)

        def blocks(x):
            return x.reshape(B, S // QB, QB, H, -1).transpose(1, 0, 2, 3, 4)

        out = jax.lax.map(block, (blocks(qn), blocks(qr),
                                  jnp.arange(0, S, QB)))
        return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)

    def flash(qn, qr, kn, kr, v):
        return flash_attention(qn, kn, v, q_rope=qr, k_rope=kr)

    def loss(fn, *a):
        return (fn(*a).astype(jnp.float32) * ct.astype(jnp.float32)).sum()

    out = jax.jit(flash)(*ops)
    grads = jax.jit(jax.grad(lambda *a: loss(flash, *a),
                             argnums=range(5)))(*ops)
    with jax.default_matmul_precision("highest"):
        out_r = jax.jit(ref)(*ops)
        grads_r = jax.jit(jax.grad(lambda *a: loss(ref, *a),
                                   argnums=range(5)))(*ops)
    name = f"flash mla ({B},{S},{H},{D}+{R})"
    _check_close(f"{name} fwd", out, out_r)
    for n, g, gr in zip(("dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv"),
                        grads, grads_r):
        _check_close(f"{name} {n}", g, gr)
        if n not in ("dq_rope", "dk_rope"):
            continue
        g, gr = (np.asarray(t, np.float32) for t in (g, gr))
        worst = (np.abs(g - gr).max(axis=(1, 3))
                 / np.abs(gr).max(axis=(1, 3)))             # (B, heads)
        print(f"  {name} {n}: worst of {worst.size} (row, head) slices "
              f"{worst.max():.2e}", flush=True)
        assert worst.max() <= TOL, (name, n, worst)

    _diff_from_the_parents(
        name, ("fwd", "dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv"),
        (out, *grads), lambda fa: _out_and_grads(
            lambda qn, qr, kn, kr, v: fa.flash_attention(
                qn, kn, v, q_rope=qr, k_rope=kr), loss, ops))


def kernel_flash_blockdiff():
    """The sixth cell's attention at the cell's own shape
    (``train-sdar-blockdiff-8k-1chip``: 2 rows of [noisy ; clean] = 16,384
    positions, 32 query heads on 4 key-value heads of 128, blocks of 4):
    ``ops/attention.py block_diffusion_attention`` (one flash call a pass
    over all 16,384 rows: a noisy query tile folds the tile of its own
    noisy blocks into the softmax of the clean keys' tiles), forward and
    the gradients of q, k and v, on two rows of different content, against
    the dense float32 mask computed in query blocks, a row at a time.  The
    noisy half's first block, whose rows keep no clean key, is reported
    apart.  Each (row, key-value head, half) slice of dk and dv is also
    checked on its own: a key-value head's sums over its eight query heads
    live in scratch that the next row must not inherit, and the clean keys
    serve the query tiles of both halves.  The bound and every reading
    are printed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention import (block_diffusion_attention,
                                             block_diffusion_mask)

    B, L, H, KV, D, G, QB = 2, 8192, 32, 4, 128, 4, 256
    S = 2 * L
    ks = jax.random.split(jax.random.PRNGKey(40), 4)
    shapes = ((B, S, H, D), (B, S, KV, D), (B, S, KV, D), (B, S, H, D))
    q, k, v, ct = (jax.random.normal(kk, s, jnp.float32).astype(jnp.bfloat16)
                   for kk, s in zip(ks, shapes))
    keep = block_diffusion_mask(L, G).reshape(S // QB, QB, S)

    def ref(q, k, v):                       # one row: (1, S, ., D)
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        kk, vv = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))

        @jax.checkpoint
        def block(args):
            q_blk, rows = args                              # (1, QB, H, D)
            s = jnp.einsum("bqhd,bthd->bhqt", q_blk, kk) * D ** -0.5
            p = jax.nn.softmax(jnp.where(rows[None, None], s, -jnp.inf), -1)
            return jnp.einsum("bhqt,bthd->bqhd", p, vv)

        out = jax.lax.map(block, (
            q.reshape(1, S // QB, QB, H, D).transpose(1, 0, 2, 3, 4), keep))
        return out.transpose(1, 0, 2, 3, 4).reshape(1, S, H, D)

    def both(fn):
        def run(q, k, v, ct):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(ct)
        return jax.jit(run)

    got = both(lambda q, k, v: block_diffusion_attention(
        q, k, v, block=G, impl="flash"))(q, k, v, ct)
    with jax.default_matmul_precision("highest"):
        one = both(ref)
        rows = [one(q[r:r + 1], k[r:r + 1], v[r:r + 1],
                    ct[r:r + 1].astype(jnp.float32)) for r in range(B)]
    want = [jnp.concatenate(parts) for parts in zip(*rows)]
    name = f"flash blockdiff ({B},2x{L},{H}/{KV},{D}) g={G}"
    for n, g, gr in zip(("fwd", "dq", "dk", "dv"), got, want):
        for half, lo in (("noisy", 0), ("clean", L)):
            _check_close(f"{name} {n} {half}", g[:, lo:lo + L],
                         gr[:, lo:lo + L])
        _check_close(f"{name} {n} noisy, first block", g[:, :G], gr[:, :G])
        if n not in ("dk", "dv"):
            continue
        g, gr = (np.asarray(t, np.float32).reshape(B, 2, L, KV, D)
                 for t in (g, gr))
        worst = (np.abs(g - gr).max(axis=(2, 4))
                 / np.abs(gr).max(axis=(2, 4)))         # (B, half, KV)
        print(f"  {name} {n}: worst of {worst.size} (row, half, key-value "
              f"head) slices {worst.max():.2e} (tol {TOL:.0e})", flush=True)
        assert np.isfinite(g).all() and worst.max() <= TOL, (name, n, worst)
    _diff_from_the_parents(
        name, ("fwd", "dq", "dk", "dv"), got,
        lambda fa: both(lambda q, k, v: fa.flash_attention_halves(
            q, k, v, block=G))(q, k, v, ct))


def kernel_indexed_attention(S: int = 32768, time_it: bool = True):
    """The tenth cell's attention at the cell's own shape
    (``train-keye-dsa-32k-1chip``: one row of 32,768 positions, 32 query
    heads on 4 key-value heads of 128, an indexer of 16 heads of 64 channels
    with one key, 2,048 keys a query): ``ops/indexed_attention.py``'s
    kernels against its plain form in float32 at "highest" precision on the
    same bf16 operands.  The selection first: the kernels keep exactly
    ``sum_t min(t + 1, 2048)`` pairs and their threshold ``tau`` is the
    plain form's ``lax.top_k`` value at that rank.  Then the output, the
    row's KL and the gradients of all six operands under a seeded
    cotangent, with the plain form's selection handed to the kernels (a
    pair that flips at the threshold is the indexer's rounding, not the
    kernels' fault) and once under their own.  ``indexed_attn_dkv``'s tile
    stands keys by queries (PR 57): the kept pairs of every causal tile are
    counted in that orientation and held equal to what the forward counted,
    and the scores bit for bit to ``_index_tile``'s.  The largest difference
    of all eight from a parent checkout's kernels, where one is unpacked
    (:func:`_diff_from_the_parents`).  Each kernel's device time a call from
    a profiler trace."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.indexed_attention import (
        indexed_attention, indexer_scores, plain_selection, select)

    B, H, KV, D, NI, DI, K = 1, 32, 4, 128, 16, 64, 2048
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(54), 8)
    q, k, v = (jax.random.normal(kk, (B, S, n, D), jnp.float32).astype(bf)
               for kk, n in zip(ks, (H, KV, KV)))
    qi = jax.random.normal(ks[3], (B, S, NI, DI), jnp.float32).astype(bf)
    ki = jax.random.normal(ks[4], (B, S, DI), jnp.float32).astype(bf)
    w = jax.random.normal(ks[5], (B, S, NI), jnp.float32) * (NI * DI) ** -0.5
    ct = jax.random.normal(ks[6], (B, S, H, D), jnp.float32).astype(bf)
    ckl = jax.random.uniform(ks[7], (B, S), jnp.float32)
    ops = (q, k, v, qi, ki, w)
    name = f"indexed attention ({B},{S},{H}/{KV},{D}) k={K}"

    def both(impl, f32=False):
        def run(ops, selection, ct, ckl):
            def loss(*ops):
                r = indexed_attention(*ops, topk=K, impl=impl,
                                      selection=selection)
                return ((r.out.astype(jnp.float32)
                         * ct.astype(jnp.float32)).sum()
                        + (r.kl * ckl).sum(), r)
            if f32:
                ops = tuple(t.astype(jnp.float32) for t in ops)
            (_, r), grads = jax.value_and_grad(
                loss, argnums=range(6), has_aux=True)(*ops)
            return (r.out, r.kl) + tuple(grads), r.tile_counts
        return jax.jit(run)

    # the selection
    tau, cut = jax.jit(lambda qi, ki, w: select(qi, ki, w, K))(qi, ki, w)
    with jax.default_matmul_precision("highest"):
        mask = jax.jit(lambda qi, ki, w: plain_selection(qi, ki, w, K))(
            *(t.astype(jnp.float32) for t in (qi, ki, w)))

        @jax.jit
        def row_tau(t0, qi, ki, w, mask):   # the plain form's value at
            at = lambda x: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                x, t0, 512, 1)              # the kept rank
            sc = indexer_scores(at(qi).astype(jnp.float32),
                                ki.astype(jnp.float32), at(w))
            return jnp.where(at(mask), sc, jnp.inf).min(-1)

        tau_ref = jnp.concatenate([row_tau(t0, qi, ki, w, mask)
                                   for t0 in range(0, S, 512)], axis=1)
    gap = float(jnp.abs(tau - tau_ref).max() / jnp.abs(tau_ref).max())
    want_pairs = K * (K + 1) // 2 + (S - K) * K if S > K \
        else S * (S + 1) // 2
    print(f"  {name}: tau differs from the plain form's by {gap:.2e} of its "
          f"largest; {int((cut < S).sum())} rows cut a tie", flush=True)
    assert gap < 1e-3, gap
    for label, selection in (("the plain form's selection", mask),
                             ("its own selection", None)):
        got, counts = both("pallas")(ops, selection, ct, ckl)
        counts = np.asarray(counts, np.float64)     # float32 sums round here
        assert int(counts.sum()) == want_pairs, (label, counts.sum(),
                                                 want_pairs)
        if selection is None:
            print(f"  {name}: {int(counts.sum())} pairs kept, "
                  f"{100.0 * counts.sum() / (S * (S + 1) / 2):.2f}% of the "
                  f"causal ones; {int((counts > 0).sum())} of "
                  f"{counts.shape[1] * (counts.shape[1] + 1) // 2} causal "
                  f"512 x 512 tiles hold one", flush=True)
            want = want[:2]     # the gradients see the flipped pairs
        else:
            with jax.default_matmul_precision("highest"):
                want, _ = both("jnp", f32=True)(ops, selection, ct, ckl)
        for n, g, gr in zip(("out", "kl", "dq", "dk", "dv", "dqI", "dkI",
                             "dw"), got, want):
            _check_close(f"{name} under {label}, {n}", g, gr)
    _kept_pairs_keys_first(name, qi, ki, w, (tau, cut), counts)
    _diff_from_the_parents(
        name, ("out", "kl", "dq", "dk", "dv", "dqI", "dkI", "dw"), got,
        lambda old: _with_kernels_of(
            old, lambda: both("pallas")(ops, None, ct, ckl)[0]),
        module="indexed_attention.py")
    if not time_it:
        return
    out = os.path.join("chiprun_out", "trace_indexed_attention")
    run = both("pallas")
    jax.block_until_ready(run(ops, None, ct, ckl))
    jax.profiler.start_trace(out)
    for _ in range(3):
        jax.block_until_ready(run(ops, None, ct, ckl))
    jax.profiler.stop_trace()
    for op, ns in sorted(_traced_op_times(out).items()):
        if op.startswith(("indexer_select", "indexed_attn")):
            print(f"  {name}: {op} {np.median(ns) / 1e6:.2f} ms a call "
                  f"({len(ns)} calls)", flush=True)


def _with_kernels_of(module, run):
    """``run()`` with ``ops/indexed_attention.py`` dispatching to the four
    calls of ``module`` (a parent checkout's kernels) in this tree's place."""
    from deepspeed_tpu.ops.pallas import indexed_attention as here

    calls = ("select_call", "forward_call", "dq_call", "dkv_call")
    saved = {n: getattr(here, n) for n in calls}
    try:
        for n in calls:
            setattr(here, n, getattr(module, n))
        return run()
    finally:
        for n, f in saved.items():
            setattr(here, n, f)


def _kept_pairs_keys_first(name, qi, ki, w, sel, forward_counts):
    """The selection as ``indexed_attn_dkv`` rebuilds it, keys by queries
    (``_index_tile_t``), against the forward's, queries by keys
    (``_index_tile``), a program a (block of keys, block of queries) as the
    kernel walks them: kept pairs a tile in both orientations, and the pairs
    whose two scores differ in a bit.  The first must equal each other and
    what ``forward_call`` counted, in every tile; the second must be 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    from deepspeed_tpu.ops import indexed_attention as op
    from deepspeed_tpu.ops.pallas import indexed_attention as ia

    B, S, NI, DI = qi.shape
    bq, bk = min(op.BLOCK_Q, S), min(op.BLOCK_K, S)
    nq, nk = S // bq, S // bk

    def kernel(qi_ref, kit_ref, ki_ref, w_ref, wt_ref, tau_ref, cut_ref,
               taut_ref, cutt_ref, qk_ref, kq_ref, bits_ref):
        j, i = pl.program_id(1), pl.program_id(2)
        q0, k0 = i * bq, j * bk

        @pl.when(i == 0)
        def _():
            for ref in (qk_ref, kq_ref, bits_ref):
                ref[...] = jnp.zeros_like(ref)

        @pl.when(i >= (j * bk) // bq)
        def _():
            qk = ia._index_tile(qi_ref, kit_ref[0], w_ref[0])
            kq = ia._index_tile_t(qi_ref, ki_ref[0], wt_ref[0])
            kept_qk, _ = ia._kept((tau_ref, cut_ref), qk, q0, k0)
            kept_kq, _ = ia._kept((taut_ref, cutt_ref), kq, q0, k0,
                                  keys_first=True)
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, nq), 1)
            for ref, n in ((qk_ref, kept_qk), (kq_ref, kept_kq),
                           (bits_ref, kq.T != qk)):
                ref[0, 0] = jnp.where(lane == i,
                                      jnp.sum(n.astype(jnp.float32)),
                                      ref[0, 0])

    tau, cut = (x.reshape(B, S, 1) for x in sel)
    turned = lambda x: jnp.swapaxes(x, 1, 2)    # noqa: E731
    out = pl.BlockSpec((1, 1, 1, nq), lambda b, j, i: (b, j, 0, 0))
    live = lambda j, i: jnp.maximum(i, (j * bk) // bq)  # noqa: E731
    query = lambda width: pl.BlockSpec(      # noqa: E731
        (1, bq, width), lambda b, j, i: (b, live(j, i), 0))
    lanes = lambda height: pl.BlockSpec(     # noqa: E731
        (1, height, bq), lambda b, j, i: (b, 0, live(j, i)))
    w = w.astype(jnp.float32)
    qk, kq, bits = jax.jit(lambda *ops: pl.pallas_call(
        kernel, grid=(B, nk, nq),
        in_specs=[pl.BlockSpec((1, NI, bq, DI),
                               lambda b, j, i: (b, 0, live(j, i), 0)),
                  pl.BlockSpec((1, DI, bk), lambda b, j, i: (b, 0, j)),
                  pl.BlockSpec((1, bk, DI), lambda b, j, i: (b, j, 0)),
                  query(NI), lanes(NI), query(1), query(1), lanes(1),
                  lanes(1)],
        out_specs=[out] * 3,
        out_shape=[jax.ShapeDtypeStruct((B, nk, 1, nq), jnp.float32)] * 3,
        compiler_params=ia._params("parallel", "parallel", "arbitrary"),
        name="kept_pairs_both_ways")(*ops))(
            jnp.swapaxes(qi, 1, 2), turned(ki), ki, w, turned(w), tau, cut,
            turned(tau), turned(cut))
    qk, kq, bits = (np.asarray(x[:, :, 0], np.float64) for x in (qk, kq, bits))
    # the forward's counts a (512 x 512) statistics tile: sum ours to those
    T = op.stat_tile(S)
    fold = lambda x: x.reshape(B, S // T, T // bk, S // T, T // bq).sum(  # noqa: E731
        (2, 4)).transpose(0, 2, 1)
    tiles = int((qk > 0).sum())
    print(f"  {name}: kept pairs a tile keys by queries, {int(kq.sum())} in "
          f"{tiles} tiles of {bk} x {bq}: {int((kq != qk).sum())} tiles "
          f"differ from queries by keys, {int(bits.sum())} scores differ in "
          f"a bit, {int((fold(kq) != forward_counts).sum())} statistics "
          f"tiles differ from the forward kernel's counts", flush=True)
    assert (kq == qk).all() and not bits.any()
    assert (fold(kq) == forward_counts).all()


def kernel_qk_rows():
    """q and k as ``(B, S, H*D)`` rows through ``ops/pallas/qk_rows.py`` at
    the third and fourth cells' shapes (4 and 3 rows of 8192, 32 / 4 heads
    of 128): the rotation alone, the per-head norm + rotation, the norm
    alone, forward and ``jax.vjp``, each against ``rms_norm`` and
    ``apply_rotary`` on float32 operands in the ``(B, S, H, D)`` view.
    Positions as the model passes them, ``arange(S)[None]`` for every row,
    and a row of its own each; every row of the batch is held apart (a
    table block read past its array is garbage in rows 1.. only)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.common import rms_norm
    from deepspeed_tpu.ops import rotary

    S, H, KV, D, eps = 8192, 32, 4, 128, 1e-5
    for B, norm, rotate, own in ((4, False, True, False), (3, True, True, True),
                                 (3, True, False, False)):
        ks = jax.random.split(jax.random.PRNGKey(B + norm + rotate), 6)
        q, gq = (jax.random.normal(kk, (B, S, H * D), jnp.float32)
                 .astype(jnp.bfloat16) for kk in ks[:2])
        k, gk = (jax.random.normal(kk, (B, S, KV * D), jnp.float32)
                 .astype(jnp.bfloat16) for kk in ks[2:4])
        scales = 1 + 0.2 * jax.random.normal(ks[4], (2, D), jnp.float32)
        pos = jnp.arange(S)[None, :]
        if own:     # packed rows: each its own positions
            pos = (pos + 1000 * jnp.arange(B)[:, None]) % 8192
        q_plan = jax.ShapeDtypeStruct(q.shape, q.dtype)
        k_plan = jax.ShapeDtypeStruct(k.shape, k.dtype)
        plan = rotary.rows_plan(q_plan, k_plan, D, norm=norm)
        assert plan == ("direct", None), plan

        def rows(q, k, scales):
            return rotary.rotate_rows(
                q, k, pos if rotate else None, D, plan,
                q_scale=scales[0] if norm else None,
                k_scale=scales[1] if norm else None, eps=eps)

        def ref(q, k, scales):
            q4 = q.astype(jnp.float32).reshape(B, S, H, D)
            k4 = k.astype(jnp.float32).reshape(B, S, KV, D)
            if norm:
                q4 = rms_norm(q4, scales[0], eps)
                k4 = rms_norm(k4, scales[1], eps)
            if rotate:
                q4, k4 = rotary.apply_rotary_pos_emb(
                    q4, k4, jnp.broadcast_to(pos, (B, S)), rotary_dim=D)
            return q4.reshape(q.shape), k4.reshape(k.shape)

        def both(fn):
            def run(q, k, scales):
                out, vjp = jax.vjp(fn, q, k, scales)
                return out, vjp(tuple(g.astype(o.dtype)
                                      for g, o in zip((gq, gk), out)))
            return jax.jit(run)

        got, want = both(rows)(q, k, scales), both(ref)(q, k, scales)
        name = f"qk_rows B={B} norm={norm} rotate={rotate}"
        for n, g, w in zip(("q'", "k'", "dq", "dk", "dscale"),
                           jax.tree_util.tree_leaves(got),
                           jax.tree_util.tree_leaves(want)):
            if g.ndim != 3:
                _check_close(f"{name} {n}", g, w)
                continue
            g, w = (np.asarray(t, np.float32) for t in (g, w))
            worst = np.abs(g - w).max(axis=(1, 2)) / np.abs(w).max(axis=(1, 2))
            print(f"  {name} {n}: worst of {B} rows {worst.max():.2e}",
                  flush=True)
            assert np.isfinite(g).all() and worst.max() <= TOL, (name, n,
                                                                 worst)


def kernel_short_conv(time_it: bool = True):
    """The causal depthwise filter of ``ops/short_conv.py`` in both its forms,
    each at its cell's shape: the gated one at the seventh cell's ``(4, 8192,
    3 x 2048)`` rows and 3 taps (PR 45), the ungated one with silu at the
    eighth cell's ``(3, 8192, 8192)`` rows and 4 taps (PR 51).  The Pallas
    row kernels, forward and ``jax.vjp`` (d rows and d taps), against
    ``benchmark/reference/lfm2.py``'s explicit loop over taps in float32;
    every row of the batch held apart (a halo read from the row before shows
    in rows 1.. only) and the first and last positions of each row alone
    (where the halo is all of the filter's reach).  XLA's shifted form is
    held to the same reference, and both are timed, forward + backward,
    against the least the HBM rate allows for the vectors the filter has to
    move; the kernels' custom calls also one by one from a profiler trace:
    which one ``auto`` takes is a reading (PERF.md section 6)."""
    import tempfile
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness.manifest import ROOT, load_module
    from deepspeed_tpu.ops.short_conv import causal_conv_rows, short_conv_rows

    reference = load_module(ROOT, "reference", "lfm2")
    f, loop = reference._f32, reference._filter

    def gated_ref(bcu, w):
        C = w.shape[0]
        z = f(bcu[..., :C]) * f(bcu[..., 2 * C:])
        return f(bcu[..., C:2 * C]) * loop(z, f(w), None, None)

    # name, the op, its reference, (B, S, C, L), thirds a row, vectors moved
    forms = (("short_conv", short_conv_rows, gated_ref,
              (4, 8192, 2048, 3), 3, 11),
             ("causal_conv silu",
              lambda x, w, impl: causal_conv_rows(x, w, "silu", impl),
              lambda x, w: jax.nn.silu(loop(f(x), f(w), None, None)),
              (3, 8192, 8192, 4), 1, 5))
    for form, op, ref, (B, S, C, L), thirds, vectors in forms:
        ks = jax.random.split(jax.random.PRNGKey(45), 3)
        rows = jax.random.normal(ks[0], (B, S, thirds * C),
                                 jnp.float32).astype(jnp.bfloat16)
        w = jax.random.normal(ks[1], (C, L), jnp.float32)
        dy = jax.random.normal(ks[2], (B, S, C),
                               jnp.float32).astype(jnp.bfloat16)

        def both(fn):
            def run(rows, w):
                out, vjp = jax.vjp(fn, rows, w)
                return (out,) + vjp(dy.astype(out.dtype))
            return jax.jit(run)

        want = [np.asarray(t, np.float32) for t in both(ref)(rows, w)]
        edge = list(range(8)) + list(range(S - 8, S))
        for impl in ("pallas", "shift"):
            run = both(lambda rows, w: op(rows, w, impl))
            got = jax.block_until_ready(run(rows, w))
            for n, g, r in zip(("y", "d rows", "d taps"), got, want):
                name = f"{form} {impl} {n}"
                if g.ndim != 3:
                    _check_close(name, g, r)
                    continue
                g = np.asarray(g, np.float32)
                worst = np.abs(g - r).max(axis=(1, 2)) \
                    / np.abs(r).max(axis=(1, 2))
                ends = np.abs(g - r)[:, edge].max() / np.abs(r)[:, edge].max()
                print(f"  {name}: worst of {B} rows {worst.max():.2e}, first "
                      f"and last 8 positions {ends:.2e}", flush=True)
                assert np.isfinite(g).all() \
                    and max(worst.max(), ends) <= TOL, (name, worst, ends)
            if not time_it:
                continue
            t0 = time.perf_counter()
            for _ in range(20):
                out = run(rows, w)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / 20 * 1e3
            print(f"  {form} {impl}: forward + backward {ms:.3f} ms "
                  f"(least for {vectors} vectors of bf16 at 819 GB/s: "
                  f"{vectors * B * S * C * 2 / 819e9 * 1e3:.3f} ms)",
                  flush=True)
            if impl == "pallas":
                out = tempfile.mkdtemp(prefix="short_conv_trace_")
                with jax.profiler.trace(out):
                    for _ in range(5):
                        jax.block_until_ready(run(rows, w))
                for call, ns in sorted(_traced_op_times(out).items()):
                    if "conv_rows" in call:
                        print(f"  {form} pallas: {call} {len(ns)} calls, "
                              f"{np.mean(ns) / 1e6:.3f} ms a call",
                              flush=True)
        del want, got, rows, dy


def kernel_moe_swiglu(R: int = 262144, F: int = 896, live_share=0.248):
    """The SwiGLU between a share's grouped matmuls (PR 61;
    ``ops/pallas/moe_rows.py swiglu_rows`` / ``swiglu_rows_back``) at
    Mellum 2's shape, ``[a | b]`` (262144, 1792) with 24.8% of the rows
    holding a pair and NaN in the others: both passes against ``jax.numpy``
    in float32 over the live rows, then timed from a profiler trace beside
    XLA's elementwise form over the whole buffer (what the program ran
    before), each with its GB/s over the bytes of the LIVE rows."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas import moe_rows

    n_live = int(R * live_share) // 16 * 16 + 8     # ends inside a block
    live = jnp.full((1,), n_live, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(61), 2)
    dead = (jnp.arange(R) >= n_live)[:, None]
    ab = jnp.where(dead, jnp.nan, 2 * jax.random.normal(
        ks[0], (R, 2 * F), jnp.float32)).astype(jnp.bfloat16)
    dh = jnp.where(dead, jnp.nan, jax.random.normal(
        ks[1], (R, F), jnp.float32)).astype(jnp.bfloat16)

    def plain(ab):
        a, b = jnp.split(ab, 2, axis=1)
        return jax.nn.silu(a) * b

    def ref(ab, dh):
        out, vjp = jax.vjp(plain, ab[:n_live].astype(jnp.float32))
        return out, vjp(dh[:n_live].astype(jnp.float32))[0]

    def xla(ab, dh):
        out, vjp = jax.vjp(plain, ab)
        return out, vjp(dh)[0]

    def rows(ab, dh):
        return (moe_rows.swiglu_rows(ab, live),
                moe_rows.swiglu_rows_back(dh, ab, live))

    want = jax.jit(ref)(ab, dh)
    got = jax.block_until_ready(jax.jit(rows)(ab, dh))
    for n, g, w in zip(("h", "d[a | b]"), got, want):
        _check_close(f"moe_swiglu {n}", g[:n_live], w)
    out = tempfile.mkdtemp(prefix="moe_swiglu_trace_")
    runs = {"rows": jax.jit(rows), "xla": jax.jit(xla)}
    for run in runs.values():
        jax.block_until_ready(run(ab, dh))
    with jax.profiler.trace(out):
        for _ in range(5):
            for run in runs.values():
                jax.block_until_ready(run(ab, dh))
    moved = {"fwd": 6 * n_live * F, "bwd": 14 * n_live * F}
    for call, ns in sorted(_traced_op_times(out).items()):
        if len(ns) < 5 or np.mean(ns) < 5e4:
            continue
        ms = np.mean(ns) / 1e6
        pas = "bwd" if "back" in call else "fwd"
        print(f"  moe_swiglu: {call} {len(ns)} calls, {ms:.3f} ms a call"
              + (f", {moved[pas] / ms / 1e6:.0f} GB/s over the {pas} pass's "
                 f"live bytes" if call.startswith("moe_swiglu") else ""),
              flush=True)
    print(f"  moe_swiglu: {n_live} of {R} rows live; bytes of the live rows "
          f"forward {moved['fwd'] / 1e6:.0f} MB, backward "
          f"{moved['bwd'] / 1e6:.0f} MB (at 819 GB/s: "
          f"{moved['fwd'] / 819e6:.3f} and {moved['bwd'] / 819e6:.3f} ms)",
          flush=True)


KERNEL_CASES = (kernel_flash, kernel_flash_window_gqa,
                kernel_flash_two_products, kernel_flash_blockdiff,
                kernel_flash_lanes_256, kernel_indexed_attention,
                kernel_gated_delta,
                kernel_gated_delta_wide, kernel_gated_delta_channels,
                kernel_gated_norm, kernel_mhc_rows, kernel_head_slots,
                kernel_qk_rows,
                kernel_short_conv,
                kernel_grouped_matmul,
                kernel_share_dispatch, kernel_full_dispatch, kernel_moe_swiglu,
                kernel_adam8bit,
                kernel_decode_attention, kernel_paged_attention,
                kernel_decode_layer, kernel_w8_matmul)


def phase_kernels():
    for case in KERNEL_CASES:
        case()


# ---------------------------------------------------------------------------
# phase 2: trainer
# ---------------------------------------------------------------------------

def phase_trainer():
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config

    seq = 1024
    cfg = gpt2_config("gpt2-1.5b", n_positions=seq, scan_layers=False,
                      remat=True, remat_policy="dots_saveable+flash",
                      attn_impl="auto", loss_chunk=8192)
    model = GPT2LMHeadModel(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw8bit",
                      "params": {"lr": 1e-4, "weight_decay": 0.1}},
        "zero_optimization": {"stage": 3},
        "mesh": {"fsdp": -1},
        "steps_per_print": 10**6,
    })
    print(f"  mesh {dict(engine.mesh.shape)}  global batch "
          f"{engine.train_batch_size} x {seq}", flush=True)
    engine.init_params()
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(engine.train_batch_size, seq)
    ).astype(np.int32)
    batch = engine.prepare_batch({"input_ids": ids, "labels": ids})
    losses = []
    for i in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        loss = float(jax.block_until_ready(engine.train_batch(batch)))
        print(f"  step {i}: loss {loss:.4f}  "
              f"{time.perf_counter() - t0:.2f}s", flush=True)
        losses.append(loss)
    if not all(np.isfinite(losses)):
        raise AssertionError(f"trainer: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"trainer: loss did not fall on the repeated "
                             f"batch: {losses}")
    state_bytes = _per_device_bytes(engine.state)
    print("  train state bytes per device: "
          + "  ".join(f"{d}={b / 2**30:.2f}GiB"
                      for d, b in sorted(state_bytes.items())), flush=True)
    n_dev = len(jax.devices())
    if n_dev > 1:
        total = sum(state_bytes.values())
        if len(state_bytes) != n_dev or \
                max(state_bytes.values()) > 0.5 * total:
            raise AssertionError(
                f"trainer: ZeRO-3 state is not split over {n_dev} devices: "
                f"{state_bytes}")
    # free the 1.5B state and unregister the trainer's mesh: the server
    # below must neither share HBM with it nor inherit its mesh through
    # comm.get_mesh
    engine._state = None
    del engine, batch
    gc.collect()
    from deepspeed_tpu.comm import mesh as mesh_mod

    mesh_mod.set_mesh(None)


def _per_device_bytes(tree):
    """``{device id: resident bytes}`` of a pytree of arrays."""
    import jax

    from deepspeed_tpu.telemetry.memory import per_device_shard_bytes

    per_dev, _ = per_device_shard_bytes(jax.tree_util.tree_leaves(tree))
    return {d.id: b for d, b in per_dev.items()}


# ---------------------------------------------------------------------------
# phase 3: server
# ---------------------------------------------------------------------------

def phase_server():
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ContinuousBatcher
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_config
    from deepspeed_tpu.telemetry import registry

    cfg = gpt2_config("gpt2-760m")
    model = GPT2LMHeadModel(cfg)
    params = jax.jit(lambda r: model.init(
        r, np.zeros((1, 8), np.int32))["params"])(jax.random.PRNGKey(0))
    chain = 1024 // PAGE_TOKENS
    eng = deepspeed_tpu.init_inference(
        model=model, params=params, max_tokens=1024,
        prefix_cache={"page_tokens": PAGE_TOKENS,
                      # every slot's worst-case chain, the trash page, and
                      # room for the radix tree to keep retired prefixes
                      "n_pages": N_SLOTS * chain + 4 * chain + 2})
    del params
    held = sorted(_per_device_bytes(eng.params))
    print(f"  server mesh {dict(eng.mesh.shape)}; weights on devices "
          f"{held} of {len(jax.devices())}", flush=True)
    if len(held) != 1:
        raise AssertionError(
            f"server: a default init_inference replicated or split its "
            f"weights over devices {held}; replicas are the router's job")
    batcher = ContinuousBatcher(eng, n_slots=N_SLOTS)
    if batcher.paged is None:
        raise AssertionError("server: paged decode did not resolve")
    batcher.warmup_windows(SERVE_TICKS)

    # a dozen requests in two pow2 prompt buckets (~48 and ~400 tokens);
    # every third shares a two-page prefix so hit-admission runs (below)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, 2 * PAGE_TOKENS + 9)
    prompts = []
    for i in range(12):
        n = (48, 400)[i % 2] - int(rng.integers(0, 8))
        p = rng.integers(0, cfg.vocab_size, n)
        if i % 3 == 0:
            n = max(n, 2 * PAGE_TOKENS + 40)
            p = np.concatenate([shared, rng.integers(
                0, cfg.vocab_size, n - len(shared))])
        prompts.append(p.astype(np.int32))

    def count(name):
        return registry.counter(name).total()

    before = {n: count(n) for n in (
        "serving_gather_pages_total", "decode_fused_qkv_traces_total",
        "decode_fused_post_attn_traces_total", "decode_fused_fallback_total",
        "prefix_cache_hit_tokens_total")}
    t0 = time.perf_counter()
    # two waves: a retiring request donates its prompt's pages to the
    # radix tree, so the second wave's shared-prefix requests hit them
    outs = []
    for wave in (prompts[:6], prompts[6:]):
        outs += batcher.run(wave, ticks=SERVE_TICKS,
                            max_new_tokens=NEW_TOKENS)
    dt = time.perf_counter() - t0
    moved = {n: count(n) - v for n, v in before.items()}
    print(f"  {len(prompts)} requests x {NEW_TOKENS} new tokens in "
          f"{dt:.2f}s; counters moved: {moved}", flush=True)
    for p, o in zip(prompts, outs):
        if o is None or len(o) - len(p) != NEW_TOKENS:
            raise AssertionError(
                f"server: request with a {len(p)}-token prompt returned "
                f"{None if o is None else len(o) - len(p)} new tokens, "
                f"asked {NEW_TOKENS}")
        if not ((0 <= o) & (o < cfg.padded_vocab_size)).all():
            raise AssertionError("server: token id out of range")
    if moved["serving_gather_pages_total"]:
        raise AssertionError("server: paged serving gathered pages")
    # the warm-up above traced the decode windows, so the fused counters
    # are read against process start, the fallback counter likewise
    if not count("decode_fused_qkv_traces_total") or \
            not count("decode_fused_post_attn_traces_total"):
        raise AssertionError("server: fused decode kernels never traced")
    if count("decode_fused_fallback_total"):
        raise AssertionError("server: fused decode fell back")
    if not moved["prefix_cache_hit_tokens_total"]:
        raise AssertionError("server: no shared-prefix admission hit")
    leaks = batcher.leak_counts()
    if any(leaks.values()):
        raise AssertionError(f"server: leaked after drain: {leaks}")
    print(f"  leak_counts {leaks}", flush=True)


# ---------------------------------------------------------------------------
# phase 4: dispatch report
# ---------------------------------------------------------------------------

# sites whose guards say "supported" at the shapes phases 2 and 3 run:
# resolving to anything else on the chip fails the run
EXPECTED = {
    "attention": "flash",             # 1.5B: head_dim 64, seq 1024
    "decode_attention": "paged_kernel",
    "decode_fused": "kernel",         # 760m: n_embd 1536 % 128 == 0
    "paged_decode": "paged",
}


def phase_dispatch():
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    rows = dispatch_report()
    for site, impl, reason, n in rows:
        print(f"  {site}: {impl} ({reason}) x{n}", flush=True)
    seen = {}
    for site, impl, _, _ in rows:
        seen.setdefault(site, set()).add(impl)
    for site, want in EXPECTED.items():
        if want not in seen.get(site, ()):
            raise AssertionError(
                f"dispatch: {site} never resolved to {want!r} "
                f"(saw {sorted(seen.get(site, ()))})")
    bad = [(s, i) for s, impls in seen.items() for i in impls
           if i == "interpret"]
    if bad:
        raise AssertionError(f"dispatch: interpreted kernels on the chip: "
                             f"{bad}")


# ---------------------------------------------------------------------------

def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (jax.devices()[0].platform "
                 f"== {dev.platform!r}); this script proves the system on "
                 f"the chip and does not fall back to another backend")
    import jaxlib

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:           # metadata only; never gates the run
        libtpu = "unknown"
    print(f"device {device}  jax {jax.__version__}  jaxlib "
          f"{jaxlib.__version__}  libtpu {libtpu}", flush=True)
    print(f"compile cache: {cache_dir}", flush=True)

    compile_s = [0.0]
    backend_compiles = []
    cache_events = {"hits": 0, "misses": 0}

    def on_duration(event, secs, **_):
        # lowering + backend compile (a cache fetch counts as the latter);
        # tracing is left out, its events nest and would count twice
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            compile_s[0] += secs
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs
            backend_compiles.append(secs)

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    t_all = time.perf_counter()
    for name, phase in (("kernels", phase_kernels), ("trainer", phase_trainer),
                        ("server", phase_server),
                        ("dispatch", phase_dispatch)):
        print(f"[{name}]", flush=True)
        c0, t0 = compile_s[0], time.perf_counter()
        phase()
        wall = time.perf_counter() - t0
        comp = compile_s[0] - c0
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()]
        print(f"[{name}] ok  wall {wall:.1f}s  compile {comp:.1f}s "
              f"({100 * comp / max(wall, 1e-9):.0f}%)  peak_bytes_in_use "
              f"so far, per device: "
              + " ".join(f"{p / 2**30:.2f}GiB" for p in peaks), flush=True)
    small = [s for s in backend_compiles if s < 1.0]
    print(f"total {time.perf_counter() - t_all:.1f}s  executables "
          f"{len(backend_compiles)} ({len(small)} built or fetched in under "
          f"1.0 s, {sum(small):.1f}s together)  persistent cache hits "
          f"{cache_events['hits']} misses {cache_events['misses']}",
          flush=True)
    entries = os.listdir(cache_dir) if os.path.isdir(cache_dir) else []
    print(f"compile cache {cache_dir}: {len(entries)} files", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
