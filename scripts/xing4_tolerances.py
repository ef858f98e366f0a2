#!/usr/bin/env python3
"""Read, on the chip and at the timed sizes, what each tolerance of
``benchmark/configs/xing4.0-29b-a4b-z3-8bit.json`` ``reference_check`` must
refuse: the plain reference with both operands of every matrix
multiplication (and the lanes every mix reads) rounded to float8 e4m3 - both
losses, the latent attention of a sparse block and of the prediction block,
every expert layer, the prediction block's per-position loss, the dense
layer, every sublayer's hyper-connection - and each computed wrong in its
named ways (``reference.FAULTS`` with YaRN's three, ``EXPERT_FAULTS``,
``MTP_FAULTS``, ``DENSE_FAULTS``, ``MHC_FAULTS``: one sweep for 20, rows
only, H_post without its 2, softmax for the sigmoid of H_pre, H_res
transposed, the norm's rsqrt left out, the clamp left out), each against
what the PROGRAM computes on the same weights and inputs, through the cell's
own readers (``benchmark/drivers/train_joyai.py read_*`` and
``train_xing4.py read_mhc``).  ``scripts/joyai_tolerances.py``'s form.
Prints one line a reading; the numbers go into the configuration file's
``*_reason`` and PERF.md by hand.

    chiprun -- python3 scripts/xing4_tolerances.py [--refuse] [--mhc] [seed..]

``--mhc`` reads the hyper-connections and the losses alone (the new limit:
~1 min a seed after the compiles).  ``--refuse`` then holds the limits that
were written to those readings: every reading of a sound reference has to
lie under its limit and every control over it, float8 by at least one of
the cell's limits; exit code 1 where one does not.  ``--rehearse`` runs the
same control flow at the configuration's CPU sizes (where the limits mean
nothing: no exit code).
"""
import json
import sys
import types

from mellum2_cell import build as _build

CELL = "train-xing4-mhc-8k-1chip"
FP8 = (4, 3)
UNSEEN = "bf16_accumulation"        # joyai_tolerances.py says why


def readings(seed, rehearse, mhc_only=False):
    """``{comparison: {control: reading}}`` of one seed, then the names of
    the readings on the wrong side of their limit."""
    import numpy as np

    cell, driver, engine, cfg, conf, batches = _build(seed, rehearse,
                                                      cell=CELL)
    joyai = driver.train_joyai
    reference = cell.reference()
    ids = next(batches)["input_ids"][:1]
    params = engine.state.params
    kw = driver.reference_kwargs(conf)
    lam = float(conf["model_options"]["mtp_loss_weight"])
    out = {}

    def say(what, **r):
        out.setdefault(what, {}).update(r)
        print(json.dumps(dict(seed=seed, what=what, **r)), flush=True)

    got, got_main, got_mtp = joyai.read_losses(engine, ids)
    hc_in = driver._OnTheHost()
    ref = joyai.reference_forward(reference, params, ids, conf,
                                  hc_inputs=hc_in)
    ref8 = joyai.reference_forward(reference, params, ids, conf,
                                   operand_bits=FP8)

    def parts(r):
        main, second = float(r["main_nll"].mean()), float(r["mtp_nll"].mean())
        return {"total": got - (main + lam * second),
                "main": got_main - main, "second": got_mtp - second}

    say("loss", engine=got, sound=parts(ref), fp8=parts(ref8))
    del ref8
    leaves = joyai.blocks(reference, params, cfg)
    read = lambda **extra: driver.read_mhc(  # noqa: E731
        seed, cfg, conf, reference, leaves, hc_in, **extra)
    say("mhc", sound=read(), fp8=read(operand_bits=FP8),
        **{f: read(fault=f) for f in reference.MHC_FAULTS})
    if not mhc_only:
        yarn = {"rope_scaling": conf["rope_scaling"]}
        for i in (cfg.num_dense_layers, len(leaves) - 1):
            read = lambda **extra: joyai.read_attention(  # noqa: E731
                cfg, reference, leaves[i]["self_attn"], ref["attn_in"][i],
                kw, **yarn, **extra)
            say(f"attention_{i}", sound=read(), fp8=read(operand_bits=FP8),
                **{f: read(fault=f) for f in reference.FAULTS})
        read = lambda **extra: joyai.read_experts(  # noqa: E731
            seed, cfg, conf, reference, leaves, ref["ffn_in"], **extra)
        say("experts", sound=read(), fp8=read(operand_bits=FP8),
            **{f: read(fault=f) for f in reference.EXPERT_FAULTS})
        mine = joyai.program_mtp_nll(cfg, params, ref["h"], ids)
        read = lambda **extra: driver._rel_err(  # noqa: E731
            mine, reference.mtp(ref["h"], ids, params, **kw, **extra))
        say("mtp", sound=read(), fp8=read(operand_bits=FP8),
            **{f: read(fault=f) for f in reference.MTP_FAULTS})
        for name, extra in ([("sound", {}), ("fp8", {"operand_bits": FP8})]
                            + [(f, {"fault": f})
                               for f in reference.DENSE_FAULTS]):
            said = []
            joyai.check_dense(
                types.SimpleNamespace(log=said.append,
                                      check=lambda ok, note: ok),
                cfg, conf, types.SimpleNamespace(
                    layers=reference.layers,
                    dense_ffn=lambda p, h: reference.dense_ffn(p, h, **extra)),
                params, ref["ffn_in"])
            say("dense", **{name: float(said[0].rsplit(" ", 1)[1])})
    del engine
    tol = conf["reference_check"]
    limits = {"attention": tol["attention_rel_tol"],
              "experts": tol["expert_rel_tol"], "mtp": tol["mtp_rel_tol"],
              "dense": tol["dense_rel_tol"], "mhc": tol["mhc_rel_tol"]}
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
    flags = {"--rehearse", "--refuse", "--mhc"}
    seeds = [int(a) for a in sys.argv[1:] if a not in flags] or [3000000021]
    rehearse = "--rehearse" in sys.argv
    wrong = {seed: readings(seed, rehearse, "--mhc" in sys.argv)
             for seed in seeds}
    print(json.dumps({"what": "refusals", "wrong": wrong}), flush=True)
    if "--refuse" in sys.argv and any(wrong.values()) and not rehearse:
        sys.exit(1)
