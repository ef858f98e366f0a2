"""The one dispatch ladder of ``ops/attention.py`` (PR 44): every form of
attention (causal; a sliding window; grouped queries that the kernels group,
head_dim 128, and that are repeated, head_dim 64; a second score product;
block diffusion over ``[noisy ; clean]`` rows) under every plan (one device;
batch axes; heads over ``tp``; a mesh ``kernel_mesh_plan`` refuses; not a
TPU; a short sequence; widths that do not tile; an implementation asked for
by name) resolves to the ``(impl, reason)`` that the three ladders of the
parent commit gave ``kernel_dispatch_total{site="attention"}``: ``_parent``
is written out from what commit 3fb1f7b booked when these same cases were run
through its ``dot_product_attention`` / ``block_diffusion_attention``.  The benchmark's
drivers grep these labels.  Nothing runs: a call is traced
(``jax.eval_shape``), which is when the dispatcher decides and counts.
"""
import math

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.ops import attention as attention_lib
from deepspeed_tpu.ops.pallas.spmd import dispatch_report

# form -> (heads, key-value heads, head_dim, head_dim that does not tile,
# keywords); "rope" is the second product's width, "block" block diffusion's
FORMS = {
    "causal": (4, 4, 64, 96, {}),
    "window": (4, 4, 64, 96, {"window": 64}),
    "grouped_128": (4, 2, 128, 96, {}),
    "repeated_64": (4, 2, 64, 32, {}),
    "second_product": (4, 4, 128, 16, {"rope": 64}),
    "block_diffusion": (4, 2, 128, 96, {"block": 4}),
}
# plan -> (mesh axes or None for no mesh, rows, a TPU, sequence, untiled
# widths, impl)
PLANS = {
    "one_device": ({"dp": 1}, 2, True, 256, False, "auto"),
    "fsdp": ({"fsdp": 8}, 8, True, 256, False, "auto"),
    "tp": ({"dp": 4, "tp": 2}, 4, True, 256, False, "auto"),
    "refused_mesh": (None, 2, True, 256, False, "auto"),
    "not_a_tpu": ({"dp": 1}, 2, False, 256, False, "auto"),
    "sequence_64": ({"dp": 1}, 2, True, 64, False, "auto"),
    "untiled_widths": ({"dp": 1}, 2, True, 256, True, "auto"),
    "flash_asked": ({"dp": 1}, 2, False, 256, False, "flash"),
    "jnp_asked": ({"dp": 1}, 2, True, 256, False, "jnp"),
}

ROWS_1, ROWS_2 = ("rows layout, 1 head a 128-lane block",
                  "rows layout, 2 heads a 128-lane block")
BLOCKS = "block diffusion over [noisy ; clean], block length 4"
# form -> what a flash reason says after the plan; the width it refuses
SAID = {
    "causal": ((ROWS_2,), "auto: head_dim 96 not in (64, 128, 256)"),
    "window": ((ROWS_2, "window 64"),
               "auto: head_dim 96 not in (64, 128, 256)"),
    "grouped_128": ((ROWS_1, "2 query heads a key-value head"),
                    "auto: head_dim 96 not in (64, 128, 256)"),
    "repeated_64": ((ROWS_2, "k and v repeated 2x to q's heads"),
                    "auto: head_dim 32 not in (64, 128, 256)"),
    "second_product": ((ROWS_1, "128 + 64 shared rope lanes, v 128"),
                       "no two-product kernel at 16 + 8 rope lanes, v 16"),
    "block_diffusion": ((ROWS_1, BLOCKS, "2 query heads a key-value head"),
                        "auto: head_dim 96 not in (64, 128, 256)"),
}


def _parent(form, plan):
    """``(impl, reason)`` of the parent commit's ladders, label for label."""
    said, untiled = SAID[form]
    tpu = "auto: TPU, seq >= 128, head_dim tiles"
    flash = {
        "one_device": (tpu, "one device"),
        "fsdp": (tpu, "shard_map over batch axes ('fsdp',)"),
        "tp": (tpu, "shard_map over batch axes ('dp',)"),
        "flash_asked": ("impl='flash' requested", "one device"),
    }
    xla = {
        "refused_mesh": "kernel_mesh_plan refused the mesh",
        "not_a_tpu": "auto: not a TPU",
        "sequence_64": "auto: seq 64 < 128",
        "untiled_widths": untiled,
        "jnp_asked": "impl='jnp' requested",
    }
    if form in ("second_product", "block_diffusion"):   # no heads over tp
        xla["tp"] = xla["refused_mesh"]
    if plan not in xla:
        return "flash", "; ".join(flash[plan] + said)
    dense = f"; {BLOCKS}, dense mask" if form == "block_diffusion" else ""
    return "jnp", xla[plan] + dense


def _operands(form, plan):
    """``(q, k, v, keywords)`` of a case, shapes only."""
    H, KV, D, untiled, kw = FORMS[form]
    _, B, _, S, no_tile, impl = PLANS[plan]
    kw = dict(kw, impl=impl)
    if no_tile:
        D = untiled
    if "block" in kw:
        S *= 2
        kw["block_diffusion"] = kw.pop("block")

    def arg(heads, width):
        return jax.ShapeDtypeStruct((B, S, heads, width), jnp.float32)

    if "rope" in kw:
        R = kw.pop("rope") if not no_tile else kw.pop("rope") // 8
        kw.update(q_rope=arg(H, R), k_rope=arg(1, R))
    return arg(H, D), arg(KV, D), arg(KV, D), kw


def _trace(q, k, v, kw):
    """Trace one call: the dispatcher decides, and counts, at trace time."""
    arrays = {n: kw[n] for n in ("q_rope", "k_rope") if n in kw}
    rest = {n: x for n, x in kw.items() if n not in arrays}
    jax.eval_shape(lambda q, k, v, arrays: attention_lib.
                   dot_product_attention(q, k, v, **arrays, **rest),
                   q, k, v, arrays)


def resolved(form, plan, monkeypatch):
    """``(impl, reason)`` that tracing the case adds to the counter."""
    axes, _, tpu, _, _, _ = PLANS[plan]
    monkeypatch.setattr(attention_lib, "on_tpu", lambda: tpu)

    def booked():
        return {(i, r): n for s, i, r, n in dispatch_report()
                if s == "attention"}

    mesh_lib.set_mesh(None if axes is None else mesh_lib.build_mesh(
        axes, devices=jax.devices()[:math.prod(axes.values())]))
    try:
        before = booked()
        _trace(*_operands(form, plan))
        new = [key for key, n in booked().items() if n > before.get(key, 0)]
    finally:
        mesh_lib.set_mesh(None)
    assert len(new) == 1, new       # ONE note_dispatch a call
    return new[0]


@pytest.mark.parametrize("plan", PLANS)
@pytest.mark.parametrize("form", FORMS)
def test_the_ladder_says_what_the_parents_three_said(form, plan, monkeypatch):
    assert resolved(form, plan, monkeypatch) == _parent(form, plan)


@pytest.mark.parametrize("form", FORMS)
def test_an_unknown_impl_raises_for_every_form_and_lists_the_names(form):
    q, k, v, kw = _operands(form, "one_device")
    for impl in ("xla", "flash_jax", "skip"):
        with pytest.raises(ValueError, match="'auto', 'flash', 'jnp'"):
            _trace(q, k, v, dict(kw, impl=impl))
