#!/usr/bin/env python3
"""Read, on the chip and at the timed sizes, what each tolerance of
``benchmark/configs/ouro-2.6b-z3-8bit.json`` ``reference_check`` must pass
and must refuse: the program against the plain reference computed sound,
with both operands of every product rounded to float8 e4m3, and wrong in
each of the ten named ways of ``reference.FAULTS`` (the norm only after the
last pass, the gate on the stream before the norm, one pass short, the last
exit alone, uniform exits, the last pass's mass times its gate, the
entropy's sign, no post-norms, theta 1e4, interleaved rotary), through the
cell's own readers (``benchmark/drivers/train_ouro.py``: one compiled
forward of the engine's model under the seeded gate; the first block alone
on the last pass's input under moved norm weights).  Prints one line a
reading; the numbers go into the configuration file's
``reference_check.reason`` and PERF.md by hand.

    chiprun -- python3 scripts/ouro_tolerances.py [--faults] [seed ...]

Without ``--faults`` a seed reads sound and float8 alone (what nine seeds
are asked for); with it also every named fault (a reference forward each).
``--rehearse`` runs the same control flow at the configuration's CPU sizes
(where the limits mean nothing).
"""
import json
import sys

from mellum2_cell import build as _build

CELL = "train-ouro-loop4-8k-1chip"
FP8 = (4, 3)


def _flat(errs: dict) -> dict:
    return {"loss": round(errs["loss"], 6),
            "exit_nll": round(errs["exit_nll"], 6),
            "exit_p": round(errs["exit_p"], 6),
            "pass": [round(e, 5) for e in errs["pass"]],
            "gate_grad": [round(e, 5) for e in errs["gate_grad"]],
            "gate_grad_plain": [round(e, 4) for e in errs["gate_grad_plain"]]}


def main(seeds, rehearse=False, faults=False):
    for seed in seeds:
        cell, driver, engine, cfg, conf, batches = _build(seed, rehearse,
                                                          cell=CELL)
        reference = cell.reference()
        ids = next(batches)["input_ids"][:1]
        params = driver.seeded(seed, engine.state.params)
        got = driver.program(engine.model)(params, ids)
        kept = driver.last_pass_inputs(cfg)
        wrongs = [("sound", {}), ("fp8", {"operand_bits": FP8})]
        if faults:
            wrongs += [(f, {"fault": f}) for f in reference.FAULTS]
        for name, extra in wrongs:
            want = driver.reference_parts(reference, params, ids, conf,
                                          {} if extra else kept, **extra)
            print(json.dumps(dict(seed=seed, what="loop", against=name,
                                  **_flat(driver.compare_parts(got, want)))),
                  flush=True)
        p = driver.moved(seed, 0, next(iter(reference.layers(params, 1))))
        (x,), (u,), (m,) = (kept[k] for k in ("block_inputs", "attn_inputs",
                                              "ffn_inputs"))
        sound_fp8 = wrongs[:2]
        block_faults = [(f, {"fault": f}) for f in reference.BLOCK_FAULTS] \
            if faults else []
        out = {name: round(driver.read_attention(
            cfg, reference, p["self_attn"], u.astype(cfg.dtype), **extra), 5)
            for name, extra in sound_fp8 + [
                b for b in block_faults if b[0] != "no_post_norms"]}
        print(json.dumps(dict(seed=seed, what="attention", **out)),
              flush=True)
        out = {name: round(driver.read_dense(
            cfg, reference, p, m.astype(cfg.dtype), **extra), 5)
            for name, extra in sound_fp8}
        print(json.dumps(dict(seed=seed, what="dense SwiGLU", **out)),
              flush=True)
        out = {name: round(driver.read_block(cfg, reference, p, x, **extra),
                           5) for name, extra in sound_fp8 + block_faults}
        print(json.dumps(dict(seed=seed, what="block", **out)), flush=True)
        del engine


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    main([int(a) for a in args] or [3000000021], "--rehearse" in sys.argv,
         "--faults" in sys.argv)
