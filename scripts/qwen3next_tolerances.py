#!/usr/bin/env python3
"""Read, on the chip and at the timed sizes, what each tolerance of
``benchmark/configs/qwen3-next-80b-a3b-z3-8bit.json`` ``reference_check``
must refuse: the plain reference with both operands of every product
rounded to float8 e4m3 (loss, the DeltaNet mixer and its gradients, the
attention layer, every expert layer), the DeltaNet mixer computed wrong in
twelve named ways (``reference.LINEAR_FAULTS``), the attention layer in
five (``reference.FAULTS``) and the expert layer in four
(``reference.EXPERT_FAULTS``), each against what the PROGRAM computes on
the same weights and inputs, through the cell's own readers
(``benchmark/drivers/train_qwen3next.py read_*``: under seeded norm
weights, ``A_log`` and ``dt_bias``).  Prints one line a reading; the
numbers go into the configuration file's ``*_reason`` and PERF.md by hand.

    chiprun -- python3 scripts/qwen3next_tolerances.py [--grads] [seed ...]

``--grads`` also reads the mixer's gradients under every fault (a
reference backward each: ~25 s a fault on the chip); without it the
gradients are read sound and in float8 alone.  ``--rehearse`` runs the
same control flow at the configuration's CPU sizes (where the limits mean
nothing).
"""
import json
import sys
import types

from mellum2_cell import build as _build

CELL = "train-qwen3next-gdn-8k-1chip"
FP8 = (4, 3)


def main(seeds, rehearse=False, grads=False):
    for seed in seeds:
        cell, driver, engine, cfg, conf, batches = _build(seed, rehearse,
                                                          cell=CELL)
        reference = cell.reference()
        ids = next(batches)["input_ids"][:1]
        params = engine.state.params
        kw = driver.reference_kwargs(conf)
        ctx = types.SimpleNamespace(seed=seed)
        got = float(engine.eval_batch({"input_ids": ids, "labels": ids}))
        ffn_in, mixer_in = [], []
        ce, aux = reference.loss_parts(params, ids, **kw, ffn_inputs=ffn_in,
                                       mixer_inputs=mixer_in)
        ce8, aux8 = reference.loss_parts(params, ids, **kw, operand_bits=FP8)
        print(json.dumps({"seed": seed, "what": "loss", "engine": got,
                          "sound": got - float(ce) - float(aux),
                          "fp8": got - float(ce8) - float(aux8)}),
              flush=True)
        leaves = list(reference.layers(params, len(ffn_in)))
        kinds = list(cfg.kinds)
        sound_fp8 = [("sound", {}), ("fp8", {"operand_bits": FP8})]

        i = kinds.index(driver.LINEAR)
        p = driver.moved(seed, i, leaves[i]["linear_attn"])
        h = driver.two_rows(mixer_in[i]).astype(cfg.dtype)
        wrongs = sound_fp8 + [(f, {"fault": f})
                              for f in reference.LINEAR_FAULTS]
        out = {name: round(driver.read_linear(cfg, reference, p, h, **extra),
                           5) for name, extra in wrongs}
        print(json.dumps(dict(seed=seed, what="linear attention", layer=i,
                              **out)), flush=True)
        out = {name: {k: round(v, 5) for k, v in driver.read_linear_grads(
            ctx, cfg, reference, p, h, i, **extra).items()}
            for name, extra in (wrongs if grads else sound_fp8)}
        print(json.dumps(dict(seed=seed, what="linear attention grads",
                              layer=i, **out)), flush=True)

        i = kinds.index(driver.FULL)
        p = driver.moved(seed, i, leaves[i]["self_attn"])
        h = mixer_in[i].astype(cfg.dtype)
        out = {name: round(driver.read_attention(
            cfg, reference, p, h, conf, **extra), 5) for name, extra in (
                sound_fp8 + [(f, {"fault": f}) for f in reference.FAULTS])}
        print(json.dumps(dict(seed=seed, what="attention", layer=i, **out)),
              flush=True)
        out = {name: [round(e, 5) for e in driver.read_experts(
            ctx, cfg, conf, reference, params, ffn_in, **extra)]
            for name, extra in (
                sound_fp8 + [(f, {"fault": f})
                             for f in reference.EXPERT_FAULTS])}
        print(json.dumps(dict(seed=seed, what="experts (a layer)", **out)),
              flush=True)
        del engine


if __name__ == "__main__":
    flags = {"--rehearse", "--grads"}
    seeds = [int(a) for a in sys.argv[1:] if a not in flags] or [3000000021]
    main(seeds, "--rehearse" in sys.argv, "--grads" in sys.argv)
