#!/usr/bin/env python3
"""Read, on the chip and at the timed sizes, what each tolerance of
``benchmark/configs/joyai-llm-flash-z3-8bit.json`` ``reference_check`` must
refuse: the plain reference with both operands of every matrix
multiplication rounded to float8 e4m3 (both losses, the latent attention
of a sparse block and of the prediction block, every expert layer, the
prediction block's per-position loss, the dense layer), the attention
computed wrong in eight named ways (``reference.FAULTS``), the expert layer
in six (``reference.EXPERT_FAULTS``), the prediction block in four
(``reference.MTP_FAULTS``) and the dense layer in one, each against what
the PROGRAM computes on the same weights and inputs, through the cell's own
readers (``benchmark/drivers/train_joyai.py read_*``).  Prints one line a
reading; the numbers go into the configuration file's ``*_reason`` and
PERF.md by hand.

    chiprun -- python3 scripts/joyai_tolerances.py [--refuse] [seed ...]

``--refuse`` then holds the limits that were written to those readings:
every reading of a sound reference has to lie under its limit and every
control over it by at least one of the cell's limits; exit code 1 where one
does not.  ``--rehearse`` runs the same control flow at the configuration's
CPU sizes (where the limits mean nothing: no exit code).
"""
import json
import sys
import types

from mellum2_cell import build as _build

CELL = "train-joyai-flash-8k-1chip"
FP8 = (4, 3)
# the one control no limit refuses: scores, probabilities and the output
# rounded to bf16 read 0.0003 above a sound run, whose own bf16 operands
# stand 0.004 from the float32 reference (the file's attention_reason)
UNSEEN = "bf16_accumulation"


def readings(seed, rehearse):
    """``{comparison: {control: reading}}`` of one seed."""
    import numpy as np

    cell, driver, engine, cfg, conf, batches = _build(seed, rehearse,
                                                      cell=CELL)
    reference = cell.reference()
    ids = next(batches)["input_ids"][:1]
    params = engine.state.params
    kw = driver.reference_kwargs(conf)
    lam = float(conf["model_options"]["mtp_loss_weight"])
    out = {}

    def say(what, **r):
        out.setdefault(what, {}).update(r)
        print(json.dumps(dict(seed=seed, what=what, **r)), flush=True)

    got, got_main, got_mtp = driver.read_losses(engine, ids)
    ref = driver.reference_forward(reference, params, ids, conf)
    ref8 = driver.reference_forward(reference, params, ids, conf,
                                    operand_bits=FP8)

    def parts(r):
        main, second = float(r["main_nll"].mean()), float(r["mtp_nll"].mean())
        return {"total": got - (main + lam * second),
                "main": got_main - main, "second": got_mtp - second}

    say("loss", engine=got, sound=parts(ref), fp8=parts(ref8))
    leaves = driver.blocks(reference, params, cfg)
    for i in (cfg.num_dense_layers, len(leaves) - 1):
        read = lambda **extra: driver.read_attention(  # noqa: E731
            cfg, reference, leaves[i]["self_attn"], ref["attn_in"][i], kw,
            **extra)
        say(f"attention_{i}", sound=read(), fp8=read(operand_bits=FP8),
            **{f: read(fault=f) for f in reference.FAULTS})
    read = lambda **extra: driver.read_experts(  # noqa: E731
        seed, cfg, conf, reference, leaves, ref["ffn_in"], **extra)
    say("experts", sound=read(), fp8=read(operand_bits=FP8),
        **{f: read(fault=f) for f in reference.EXPERT_FAULTS})
    mine = driver.program_mtp_nll(cfg, params, ref["h"], ids)
    read = lambda **extra: driver._rel_err(  # noqa: E731
        mine, reference.mtp(ref["h"], ids, params, **kw, **extra))
    say("mtp", sound=read(), fp8=read(operand_bits=FP8),
        **{f: read(fault=f) for f in reference.MTP_FAULTS})
    for name, extra in ([("sound", {}), ("fp8", {"operand_bits": FP8})]
                        + [(f, {"fault": f}) for f in reference.DENSE_FAULTS]):
        said = []
        driver.check_dense(
            types.SimpleNamespace(log=said.append, check=lambda ok, note: ok),
            cfg, conf, types.SimpleNamespace(
                layers=reference.layers,
                dense_ffn=lambda p, h: reference.dense_ffn(p, h, **extra)),
            params, ref["ffn_in"])
        say("dense", **{name: float(said[0].rsplit(" ", 1)[1])})
    del engine
    tol = conf["reference_check"]
    limits = {"attention": tol["attention_rel_tol"],
              "experts": tol["expert_rel_tol"], "mtp": tol["mtp_rel_tol"],
              "dense": tol["dense_rel_tol"]}
    wrong = []
    worst = lambda v: float(np.max(np.abs(v)))  # noqa: E731
    for what, r in out.items():
        if what == "loss":
            if max(abs(v) for v in r["sound"].values()) > tol["loss_abs_tol"]:
                wrong.append("loss sound")
            continue
        limit = limits[what.split("_")[0]]
        for control, v in r.items():
            if control == UNSEEN:       # a reading only
                continue
            if (worst(v) <= limit) != (control == "sound"):
                wrong.append(f"{what} {control}")
    # float8 has to be refused by ONE of the cell's limits, not by each
    fp8 = [w for w in wrong if w.endswith(" fp8")]
    if len(fp8) < sum(1 for w in out if w != "loss"):
        wrong = [w for w in wrong if not w.endswith(" fp8")]
    return wrong


if __name__ == "__main__":
    flags = {"--rehearse", "--refuse"}
    seeds = [int(a) for a in sys.argv[1:] if a not in flags] or [3000000021]
    rehearse = "--rehearse" in sys.argv
    wrong = {seed: readings(seed, rehearse) for seed in seeds}
    print(json.dumps({"what": "refusals", "wrong": wrong}), flush=True)
    if "--refuse" in sys.argv and any(wrong.values()) and not rehearse:
        sys.exit(1)
