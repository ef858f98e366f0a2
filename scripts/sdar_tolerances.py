#!/usr/bin/env python3
"""Read, on the chip and at the timed sizes, what each tolerance of
``benchmark/configs/sdar-30b-a3b-z3-8bit.json`` ``reference_check`` must
refuse: the plain reference with both operands of every matrix
multiplication rounded to float8 e4m3 (the weighted loss, every expert
layer, the first layer's attention and the attention core with its three
gradients) and the objective computed wrong in the named ways
(``reference.FAULTS``: the clean half made plain causal, the noisy half
shown its own block's clean keys, the weight 1/t dropped, the loss read
from the clean half, key-value head ``h % 4``), each against what the
PROGRAM computes on the same weights, rows, mask and levels, through the
cell's own readers (``benchmark/drivers/train_sdar.py read_*``).  Prints
one line a reading; the numbers go into the configuration file's
``*_reason`` and PERF.md by hand.

    chiprun -- python3 scripts/sdar_tolerances.py [--refuse] [seed ...]

``--refuse`` then holds the limits that were written to those readings:
every reading of a sound reference has to lie under its limit, every named
fault over the limit of the comparison that is there to see it, and float8
over at least one; exit code 1 where one does not.  ``--rehearse`` runs the
same control flow at the configuration's CPU sizes (where the limits mean
nothing: no exit code).
"""
import json
import sys

from mellum2_cell import build as _build

CELL = "train-sdar-blockdiff-8k-1chip"
FP8 = (4, 3)
# which comparison has to refuse which fault
SEEN_BY = {"no_weight": ("loss",), "clean_loss": ("loss",),
           "clean_causal": ("attention", "core"),
           "noisy_sees_own_clean": ("attention", "core"),
           "kv_mod": ("attention", "core")}


def readings(seed, rehearse):
    """The comparisons that came out wrong for one seed."""
    import numpy as np

    from benchmark.drivers import train_mellum2

    cell, driver, engine, cfg, conf, _ = _build(seed, rehearse, cell=CELL)
    from benchmark import loadgen

    mix = {k: v for k, v in cell.traffic.items() if k != "rehearse"}
    if rehearse:
        mix.update(cell.traffic["rehearse"])
    batch = next(loadgen.packed_batches(
        mix, seed, max(2, engine.train_batch_size),
        cfg.vocab_size - 1))["input_ids"]
    reference = cell.reference()
    params = engine.state.params
    kw = driver.reference_kwargs(conf)
    ids, L = batch[:1], batch.shape[1]
    mask, t = driver.seeded_noise(seed, 2, L, conf)
    out = {}

    def say(what, **r):
        out.setdefault(what, {}).update(r)
        print(json.dumps(dict(seed=seed, what=what, **r)), flush=True)

    got = float(engine.eval_batch({
        "input_ids": ids, "labels": ids, "diffusion_mask": mask[:1],
        "diffusion_t": t[:1]}))

    def loss(**extra):
        ffn_in = []
        ce, aux = reference.loss_parts(params, ids, mask[:1], t[:1], **kw,
                                       ffn_inputs=ffn_in, **extra)
        return got - float(ce) - float(aux), ffn_in

    sound, ffn_in = loss()
    say("loss", engine=got, sound=sound, fp8=loss(operand_bits=FP8)[0],
        **{f: loss(fault=f)[0] for f in reference.FAULTS})

    def experts(**extra):
        said = []
        import types

        train_mellum2.check_experts(
            types.SimpleNamespace(log=said.append, check=lambda ok, note: ok),
            cfg, conf, types.SimpleNamespace(
                layers=reference.layers,
                expert_ffn=lambda p, h, **k: reference.expert_ffn(
                    p, h, **k, **extra)), params, ffn_in)
        return [float(x) for x in said[0].split("a layer ", 1)[1].split()]

    say("experts", sound=experts(), fp8=experts(operand_bits=FP8))
    del ffn_in
    p_attn = next(iter(reference.layers(params, 1)))["self_attn"]
    h = reference.first_attention_input(
        params, batch[:2], mask, mask_token_id=kw["mask_token_id"],
        eps=kw["eps"]).astype(cfg.dtype)
    controls = [("sound", {}), ("fp8", {"operand_bits": FP8})] \
        + [(f, {"fault": f}) for f in reference.FAULTS
           if "attention" in SEEN_BY[f]]
    say("attention", **{name: driver.read_attention(
        cfg, reference, p_attn, h[:1], kw, **extra)
        for name, extra in controls})
    for name, extra in controls:
        say("core", **{name: driver.read_core(cfg, reference, p_attn, h, kw,
                                              seed, **extra)})
    del engine
    tol = conf["reference_check"]

    def over(what, r):
        """Whether reading ``r`` of comparison ``what`` passes its limit."""
        if what == "loss":
            return abs(r) > tol["loss_abs_tol"]
        if what == "experts":
            return max(r) > tol["expert_rel_tol"]
        if what == "attention":
            return max(r) > tol["attention_rel_tol"]
        return any(v > tol["core_rel_tol" if n.startswith("out")
                           else "core_grad_rel_tol"] for n, v in r.items())

    wrong = [f"{what} sound" for what, r in out.items()
             if over(what, r["sound"]) or not np.all(np.isfinite(
                 list(r["sound"].values()) if isinstance(r["sound"], dict)
                 else r["sound"]))]
    for fault, seers in SEEN_BY.items():
        wrong += [f"{what} {fault}" for what in seers
                  if not over(what, out[what][fault])]
    if not any(over(what, r["fp8"]) for what, r in out.items()):
        wrong.append("fp8 passes every limit")
    return wrong


if __name__ == "__main__":
    flags = {"--rehearse", "--refuse"}
    seeds = [int(a) for a in sys.argv[1:] if a not in flags] or [3000000021]
    rehearse = "--rehearse" in sys.argv
    wrong = {seed: readings(seed, rehearse) for seed in seeds}
    print(json.dumps({"what": "refusals", "wrong": wrong}), flush=True)
    if "--refuse" in sys.argv and any(wrong.values()) and not rehearse:
        sys.exit(1)
