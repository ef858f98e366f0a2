#!/usr/bin/env python3
"""Read, on the chip and at the timed sizes, what each tolerance of
``benchmark/configs/lfm2-24b-a2b-z3-8bit.json`` ``reference_check`` must
refuse: the plain reference with both operands of every product rounded to
float8 e4m3 (loss, the conv mixer and its gradients, the attention layer,
every expert layer, the dense layer), the conv mixer computed wrong in six
named ways (``reference.CONV_FAULTS``), the attention layer in four
(``reference.FAULTS``), the expert layer in five
(``reference.EXPERT_FAULTS``) and the dense layer in one
(``reference.DENSE_FAULTS``), each against what the PROGRAM computes on the
same weights and inputs, through the cell's own readers
(``benchmark/drivers/train_lfm2.py read_*``).  Prints one line a reading;
the numbers go into the configuration file's ``*_reason`` and PERF.md by
hand.

    chiprun -- python3 scripts/lfm2_tolerances.py [--refuse] [seed ...]

``--refuse`` then holds the limits that were written to those readings:
each control stands in the reference's place through the cell's own
comparison (``train_lfm2.check_reference`` on a harness ``Context``) and
has to come out not ``correct``, and the sound reference ``correct``; exit
code 1 where one does not.  ``--rehearse`` runs the same control flow at
the configuration's CPU sizes (where the limits mean nothing: no exit
code).
"""
import json
import sys
import time
import types

from mellum2_cell import build as _build

CELL = "train-lfm2-hybrid-8k-1chip"
FP8 = (4, 3)


def build(seed, rehearse):
    return _build(seed, rehearse, cell=CELL)


def control(reference, conv=None, attention=None, expert=None, dense=None,
            operand_bits=None):
    """The reference with one thing wrong: a named fault of one mechanism,
    or every product's operands in ``operand_bits`` (``dense`` takes
    ``{"fault": ...}`` or ``{"operand_bits": ...}`` for the dense layer
    ALONE: the loss reads a float8 dense layer inside its own noise)."""
    bits = {} if operand_bits is None else {"operand_bits": operand_bits}

    def wrong(fault):
        return {"fault": fault} if fault else {}

    return types.SimpleNamespace(
        layers=reference.layers, bias_update=reference.bias_update,
        loss_parts=lambda *a, **kw: reference.loss_parts(*a, **bits, **kw),
        short_conv=lambda *a, **kw: reference.short_conv(
            *a, **bits, **wrong(conv), **kw),
        short_conv_grads=lambda *a, **kw: reference.short_conv_grads(
            *a, **bits, **wrong(conv), **kw),
        attention=lambda *a, **kw: reference.attention(
            *a, **bits, **wrong(attention), **kw),
        expert_ffn=lambda *a, **kw: reference.expert_ffn(
            *a, **bits, **wrong(expert), **kw),
        dense_ffn=lambda *a, **kw: reference.dense_ffn(
            *a, **bits, **(dense or {}), **kw))


def controls(reference):
    return ([("sound", reference),
             ("fp8", control(reference, operand_bits=FP8))]
            + [("conv_" + f, control(reference, conv=f))
               for f in reference.CONV_FAULTS]
            + [("attention_" + f, control(reference, attention=f))
               for f in reference.FAULTS]
            + [("expert_" + f, control(reference, expert=f))
               for f in reference.EXPERT_FAULTS]
            + [("dense_fp8", control(reference,
                                     dense={"operand_bits": FP8}))]
            + [("dense_" + f, control(reference, dense={"fault": f}))
               for f in reference.DENSE_FAULTS])


def refusals(seed, rehearse):
    """Every control through the cell's comparison; the names of those that
    came out ``correct`` and should not have (or the reverse)."""
    from benchmark.harness.runner import Context

    cell, driver, engine, cfg, conf, batches = build(seed, rehearse)
    reference = cell.reference()
    first = next(batches)       # the row the run compares
    wrong = []
    for name, stand_in in controls(reference):
        ctx = Context(cell, seed, 0.0, False, rehearse, None,
                      time.perf_counter())
        driver.check_reference(ctx, engine, cfg, conf, stand_in,
                               iter([first]))
        print(json.dumps({"seed": seed, "what": "refusal", "control": name,
                          "correct": not ctx.notes,
                          "notes": [n[:120] for n in ctx.notes]}),
              flush=True)
        if (not ctx.notes) != (name == "sound"):
            wrong.append(name)
    return wrong


def main(seeds, rehearse=False):
    for seed in seeds:
        cell, driver, engine, cfg, conf, batches = build(seed, rehearse)
        reference = cell.reference()
        ids = next(batches)["input_ids"][:1]
        params = engine.state.params
        kw = driver.reference_kwargs(conf)
        ctx = types.SimpleNamespace(seed=seed)
        got = float(engine.eval_batch({"input_ids": ids, "labels": ids}))
        ffn_in, mixer_in = [], []
        ce = reference.loss_parts(params, ids, **kw, ffn_inputs=ffn_in,
                                  mixer_inputs=mixer_in)[0]
        ce8 = reference.loss_parts(params, ids, **kw, operand_bits=FP8)[0]
        print(json.dumps({"seed": seed, "what": "loss", "engine": got,
                          "sound": got - float(ce),
                          "fp8": got - float(ce8)}), flush=True)
        leaves = list(reference.layers(params, len(ffn_in)))
        kinds = list(cfg.kinds)
        wrongs = [("sound", {}), ("fp8", {"operand_bits": FP8})] \
            + [(f, {"fault": f}) for f in reference.CONV_FAULTS]
        for i in driver.conv_layers_checked(cfg):
            p = leaves[i]["conv"]
            h = driver.two_rows(mixer_in[i]).astype(cfg.dtype)
            out, grads = {}, {}
            for name, extra in wrongs:
                whole, heads = driver.read_conv(cfg, reference, p, h, **extra)
                out[name] = [round(whole, 5), round(heads, 5)]
                grads[name] = round(max(driver.read_conv_grads(
                    ctx, cfg, reference, p, h, i, **extra).values()), 5)
            print(json.dumps(dict(seed=seed, what="conv [whole, heads]",
                                  layer=i, **out)), flush=True)
            print(json.dumps(dict(seed=seed, what="conv grads (max)",
                                  layer=i, **grads)), flush=True)
        i = kinds.index(driver.FULL)
        p, h = leaves[i]["self_attn"], mixer_in[i].astype(cfg.dtype)
        readings = {name: round(driver.read_attention(
            cfg, reference, p, h, kw, **extra), 5) for name, extra in (
                [("sound", {}), ("fp8", {"operand_bits": FP8})]
                + [(f, {"fault": f}) for f in reference.FAULTS])}
        print(json.dumps(dict(seed=seed, what="attention", layer=i,
                              **readings)), flush=True)
        readings = {name: [round(e, 5) for e in driver.read_experts(
            ctx, cfg, conf, reference, params, ffn_in, **extra)]
            for name, extra in (
                [("sound", {}), ("fp8", {"operand_bits": FP8})]
                + [(f, {"fault": f}) for f in reference.EXPERT_FAULTS])}
        print(json.dumps(dict(seed=seed, what="experts (a layer)",
                              **readings)), flush=True)
        for name, extra in (
                [("sound", {}), ("fp8", {"operand_bits": FP8})]
                + [(f, {"fault": f}) for f in reference.DENSE_FAULTS]):
            said = []
            driver.train_trinity.check_dense(
                types.SimpleNamespace(log=said.append,
                                      check=lambda ok, note: ok),
                cfg, conf, types.SimpleNamespace(
                    layers=reference.layers,
                    dense_ffn=lambda p, h: reference.dense_ffn(p, h, **extra)),
                params, ffn_in)
            print(json.dumps({"seed": seed, "what": "dense", "control": name,
                              "lines": said}), flush=True)
        del engine


if __name__ == "__main__":
    flags = {"--rehearse", "--refuse"}
    seeds = [int(a) for a in sys.argv[1:] if a not in flags] or [3000000021]
    rehearse = "--rehearse" in sys.argv
    if "--refuse" not in sys.argv:
        main(seeds, rehearse)
    else:
        wrong = {seed: refusals(seed, rehearse) for seed in seeds}
        print(json.dumps({"what": "refusals", "wrong": wrong}), flush=True)
        if any(wrong.values()) and not rehearse:
            sys.exit(1)
