#!/usr/bin/env python3
"""Read, on the chip and at the timed sizes, what each tolerance of
``benchmark/configs/ling-3.0-flash-z3-8bit.json`` ``reference_check`` must
refuse: the plain reference with both operands of every product rounded to
float8 e4m3 (loss, the Kimi-Delta-Attention mixer and its gradients, the
latent-attention layer, every expert layer, the dense SwiGLU), the mixer
computed wrong in the named ways of ``reference.KDA_FAULTS`` (one decay a
head, no decay, the gate's form, a state that resets at a chunk's edge,
...), the attention layer in those of ``reference.FAULTS`` (no gate a head,
the gate in the wrong place, ...) and the experts in those of
``reference.EXPERT_FAULTS`` (no group limit, a group's best alone, ...),
each against what the PROGRAM computes on the same weights and inputs,
through the cell's own readers (``benchmark/drivers/train_ling3.py
read_*``: under seeded 1-D leaves and a seeded bias).  Prints one line a
reading; the numbers go into the configuration file's
``reference_check.*reason`` and PERF.md by hand.

    chiprun -- python3 scripts/ling3_tolerances.py [--grads[=f1,f2]] [seed ...]

``--grads`` also reads the mixer's gradients under every fault (a reference
backward each), ``--grads=no_decay,...`` under the named ones; without it
the gradients are read sound and in float8 alone.  ``--rehearse`` runs the
same control flow at the configuration's CPU sizes (where the limits mean
nothing).
"""
import json
import sys
import types

from mellum2_cell import build as _build

CELL = "train-ling3-kda-8k-1chip"
FP8 = (4, 3)


def main(seeds, rehearse=False, grads=()):
    for seed in seeds:
        cell, driver, engine, cfg, conf, batches = _build(seed, rehearse,
                                                          cell=CELL)
        reference = cell.reference()
        ids = next(batches)["input_ids"][:1]
        params = engine.state.params
        kw = driver.reference_kwargs(conf)
        ctx = types.SimpleNamespace(seed=seed)
        got = driver.read_loss(engine, ids)
        mixer_in, ffn_in = [], []
        ce, _ = reference.loss_parts(params, ids, **kw, ffn_inputs=ffn_in,
                                     mixer_inputs=mixer_in)
        ce8, _ = reference.loss_parts(params, ids, **kw, operand_bits=FP8)
        print(json.dumps({"seed": seed, "what": "loss", "engine": got,
                          "sound": got - float(ce),
                          "fp8": got - float(ce8)}), flush=True)
        leaves = driver.blocks(reference, params, cfg)
        kinds = list(cfg.kinds)
        sound_fp8 = [("sound", {}), ("fp8", {"operand_bits": FP8})]

        i = len(kinds) - 1 - kinds[::-1].index(driver.KDA)
        p = driver.moved(seed, i, leaves[i]["kda_attn"])
        h = driver.two_rows(mixer_in[i]).astype(cfg.dtype)
        wrongs = sound_fp8 + [(f, {"fault": f}) for f in reference.KDA_FAULTS]
        out = {name: {k: round(v, 5) for k, v in driver.read_kda_grads(
            ctx, cfg, reference, p, h, i, kw, **extra).items()}
            for name, extra in wrongs if name in grads or not extra
            or "operand_bits" in extra}
        print(json.dumps(dict(seed=seed, what="KDA mixer and gradients",
                              layer=i, **out)), flush=True)

        i = kinds.index(driver.FULL)
        p = driver.moved(seed, i, leaves[i]["self_attn"])
        h = mixer_in[i].astype(cfg.dtype)
        out = {name: round(driver.read_attention(cfg, reference, p, h, kw,
                                                 **extra), 5)
               for name, extra in (sound_fp8 + [(f, {"fault": f})
                                                for f in reference.FAULTS])}
        print(json.dumps(dict(seed=seed, what="attention", layer=i, **out)),
              flush=True)

        for name, extra in (sound_fp8 + [(f, {"fault": f})
                                         for f in reference.EXPERT_FAULTS]):
            errs, changed = driver.read_experts(seed, cfg, reference, leaves,
                                                ffn_in, kw, **extra)
            print(json.dumps(dict(
                seed=seed, what="experts", reading=name,
                layers=[round(e, 5) for e in errs],
                moved_by_the_group_limit=[round(c, 4) for c in changed])),
                flush=True)

        out = {}
        for name, extra in sound_fp8 + [("gate_up_swapped",
                                         {"fault": "gate_up_swapped"})]:
            notes = []
            probe = types.SimpleNamespace(
                check=lambda ok, what: ok, log=notes.append)
            wrong = types.SimpleNamespace(
                layers=reference.layers,
                dense_ffn=lambda p, h, extra=extra: reference.dense_ffn(
                    p, h, **extra))
            driver.check_dense(probe, cfg, conf, wrong, params, ffn_in)
            out[name] = float(notes[0].rsplit(" ", 1)[1])
        print(json.dumps(dict(seed=seed, what="dense SwiGLU", **out)),
              flush=True)
        del engine


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    grads = [a for a in sys.argv[1:] if a.startswith("--grads")]
    named = () if not grads else (grads[0].partition("=")[2].split(",")
                                  if "=" in grads[0] else "all")
    if named == "all":
        from benchmark.harness.manifest import ROOT, load_module

        named = load_module(ROOT, "reference", "ling3").KDA_FAULTS
    main([int(a) for a in args] or [3000000023], "--rehearse" in sys.argv,
         named)
