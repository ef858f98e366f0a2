#!/usr/bin/env python3
"""Read, on the chip and at the timed sizes, what each tolerance of
``benchmark/configs/olmo-hybrid-7b-z3-8bit.json`` ``reference_check`` must
refuse: the plain reference with both operands of every product rounded to
float8 e4m3 (loss, the DeltaNet mixer and its gradients, the attention
layer, the dense SwiGLU, the whole block), the DeltaNet mixer computed wrong
in eleven named ways (``reference.LINEAR_FAULTS``: beta without the 2, the
value width in the scale, the gate before the norm, a state that resets at a
chunk's edge, leaks across rows or is kept in bf16, ...), the attention
layer in three (``reference.FAULTS``: the rotation left on, the per-head
norm, none) and the block in one (a norm before each branch instead of
after), each against what the PROGRAM computes on the same weights and
inputs, through the cell's own readers (``benchmark/drivers/
train_olmo_hybrid.py read_*``: under seeded 1-D leaves and ``b`` rescaled).
Prints one line a reading; the numbers go into the configuration file's
``reference_check.reason`` and PERF.md by hand.

    chiprun -- python3 scripts/olmo_hybrid_tolerances.py [--grads[=f1,f2]] [seed ...]

``--grads`` also reads the mixer's gradients under every fault (a reference
backward each), ``--grads=state_bf16,...`` under the named ones; without it
the gradients are read sound and in float8 alone.  ``--rehearse`` runs the same control flow at the configuration's
CPU sizes (where the limits mean nothing).
"""
import json
import sys
import types

from mellum2_cell import build as _build

CELL = "train-olmo-hybrid-8k-1chip"
FP8 = (4, 3)
LINEAR_FAULTS_ALL = ("beta_no_two", "scale_dv", "gate_before_norm",
                     "chunk_reset", "row_leak", "state_bf16", "no_decay",
                     "no_l2norm", "taps_reversed", "no_silu", "gate_sigmoid")


def main(seeds, rehearse=False, grads=()):
    for seed in seeds:
        cell, driver, engine, cfg, conf, batches = _build(seed, rehearse,
                                                          cell=CELL)
        reference = cell.reference()
        ids = next(batches)["input_ids"][:1]
        params = engine.state.params
        kw = driver.reference_kwargs(conf)
        ctx = types.SimpleNamespace(seed=seed)
        got = float(engine.eval_batch({"input_ids": ids, "labels": ids}))
        block_in, ffn_in = [], []
        ce, _ = reference.loss_parts(params, ids, **kw, block_inputs=block_in,
                                     ffn_inputs=ffn_in)
        ce8, _ = reference.loss_parts(params, ids, **kw, operand_bits=FP8)
        print(json.dumps({"seed": seed, "what": "loss", "engine": got,
                          "sound": got - float(ce),
                          "fp8": got - float(ce8)}), flush=True)
        leaves = list(reference.layers(params, len(block_in)))
        sound_fp8 = [("sound", {}), ("fp8", {"operand_bits": FP8})]

        i, p, h = driver.linear_operands(seed, cfg, conf, leaves, block_in)
        wrongs = sound_fp8 + [(f, {"fault": f})
                              for f in reference.LINEAR_FAULTS]
        out = {name: round(driver.read_linear(cfg, reference, p, h, **extra),
                           5) for name, extra in wrongs}
        print(json.dumps(dict(
            seed=seed, what="linear attention", layer=i,
            beta_above_1p5=round(driver.beta_high_share(p, h, cfg), 4),
            **out)), flush=True)
        out = {name: {k: round(v, 5) for k, v in driver.read_linear_grads(
            ctx, cfg, reference, p, h, i, **extra).items()}
            for name, extra in wrongs if name in grads or not extra
            or "operand_bits" in extra}
        print(json.dumps(dict(seed=seed, what="linear attention grads",
                              layer=i, **out)), flush=True)

        i = list(cfg.kinds).index(driver.FULL)
        p = driver.moved(seed, i, leaves[i])
        h = block_in[i].astype(cfg.dtype)
        out = {name: round(driver.read_attention(
            cfg, reference, p["self_attn"], h, **extra), 5)
            for name, extra in (sound_fp8 + [(f, {"fault": f})
                                             for f in reference.FAULTS])}
        print(json.dumps(dict(seed=seed, what="attention", layer=i, **out)),
              flush=True)
        out = {name: round(driver.read_dense(
            cfg, reference, p, ffn_in[i].astype(cfg.dtype), driver.FULL,
            **extra), 5) for name, extra in sound_fp8}
        print(json.dumps(dict(seed=seed, what="dense SwiGLU", layer=i,
                              **out)), flush=True)
        out = {name: round(driver.read_block(
            cfg, reference, p, block_in[i], driver.FULL, **extra), 5)
            for name, extra in (sound_fp8 + [
                (f, {"fault": f}) for f in reference.BLOCK_FAULTS])}
        print(json.dumps(dict(seed=seed, what="block", layer=i, **out)),
              flush=True)
        del engine


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    grads = [a for a in sys.argv[1:] if a.startswith("--grads")]
    named = () if not grads else (grads[0].partition("=")[2].split(",")
                                  if "=" in grads[0] else LINEAR_FAULTS_ALL)
    main([int(a) for a in args] or [3000000021], "--rehearse" in sys.argv,
         named)
