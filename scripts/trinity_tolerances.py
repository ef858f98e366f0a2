#!/usr/bin/env python3
"""Read, on the chip and at the timed sizes, what each tolerance of
``benchmark/configs/trinity-mini-z3-8bit.json`` ``reference_check`` must
refuse: the plain reference with both operands of every matrix
multiplication rounded to float8 e4m3 (loss, every expert layer, the dense
layer, one attention layer of each type), the attention layer computed
wrong in six named ways (``reference.FAULTS``), the expert layer in six
(``reference.EXPERT_FAULTS``) and the dense layer in one
(``reference.DENSE_FAULTS``), each against what the PROGRAM computes on
the same weights and inputs.  Prints one line a reading; the numbers go
into the configuration file's ``*_reason`` and PERF.md by hand.

    chiprun -- python3 scripts/trinity_tolerances.py [--refuse] [seed ...]

``--dense`` reads the loss and the dense layer only (seconds a seed).

``--refuse`` then holds the limits that were written to those readings:
each control stands in the reference's place through the cell's own
comparison (``train_trinity.check_reference`` on a harness ``Context``)
and has to come out not ``correct``, and the sound reference ``correct``;
exit code 1 where one does not.  ``--rehearse`` runs the same control flow
at the configuration's CPU sizes (where the limits mean nothing: no exit
code).
"""
import json
import sys
import time
import types

from mellum2_cell import build as _build

CELL = "train-trinity-mini-8k-1chip"
FP8 = (4, 3)


def build(seed, rehearse):
    return _build(seed, rehearse, cell=CELL)


def applies(reference, fault, kind) -> bool:
    """Whether an attention fault changes a layer of this type."""
    sliding = kind == reference.SLIDING
    return not (fault == "rope_on_full" and sliding
                or fault in ("no_rope_on_sliding", "window+1") and not sliding)


def control(reference, fault=None, expert_fault=None, operand_bits=None,
            dense=None):
    """The reference with one thing wrong: an attention ``fault``, an
    ``expert_fault``, every matmul's operands in ``operand_bits``, or the
    dense layer ALONE given ``dense`` (``fault=`` or ``operand_bits=``):
    the loss reads a float8 dense layer inside its own noise, so only
    comparison 3 can refuse that one."""
    bits = {} if operand_bits is None else {"operand_bits": operand_bits}

    def attention(kind, *a, **kw):
        return reference.attention(
            kind, *a, **bits, **kw,
            **({"fault": fault} if fault and applies(reference, fault, kind)
               else {}))

    return types.SimpleNamespace(
        layers=reference.layers, attention=attention,
        bias_update=reference.bias_update,
        loss_parts=lambda *a, **kw: reference.loss_parts(*a, **bits, **kw),
        dense_ffn=lambda *a, **kw: reference.dense_ffn(
            *a, **bits, **(dense or {}), **kw),
        expert_ffn=lambda *a, **kw: reference.expert_ffn(
            *a, **bits, **kw,
            **({"fault": expert_fault} if expert_fault else {})))


def refusals(seed, rehearse):
    """Every control through the cell's comparison; the names of those that
    came out ``correct`` and should not have (or the reverse)."""
    from benchmark.harness.runner import Context

    cell, driver, engine, cfg, conf, batches = build(seed, rehearse)
    reference = cell.reference()
    first = next(batches)       # the row the run compares
    wrong = []
    for name, stand_in in (
            [("sound", reference),
             ("fp8", control(reference, operand_bits=FP8))]
            + [(f, control(reference, fault=f)) for f in reference.FAULTS]
            + [(f, control(reference, expert_fault=f))
               for f in reference.EXPERT_FAULTS]
            + [("dense_fp8", control(reference,
                                     dense={"operand_bits": FP8}))]
            + [("dense_" + f, control(reference, dense={"fault": f}))
               for f in reference.DENSE_FAULTS]):
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


def main(seeds, rehearse=False, dense_only=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.llama import LlamaAttention
    from deepspeed_tpu.parallel.moe import MoELayer

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    for seed in seeds:
        cell, driver, engine, cfg, conf, batches = build(seed, rehearse)
        reference = cell.reference()
        ids = next(batches)["input_ids"][:1]
        params = engine.state.params
        kw = driver.reference_kwargs(conf)
        got = float(engine.eval_batch({"input_ids": ids, "labels": ids}))
        ffn_in, attn_in = [], []
        ce = reference.loss_parts(params, ids, **kw, ffn_inputs=ffn_in,
                                  attn_inputs=attn_in)[0]
        ce8 = reference.loss_parts(params, ids, **kw, operand_bits=FP8)[0]
        print(json.dumps({"seed": seed, "what": "loss", "engine": got,
                          "sound": got - float(ce),
                          "fp8": got - float(ce8)}), flush=True)
        leaves = list(reference.layers(params, len(ffn_in)))
        layer = MoELayer(cfg.moe, model_dim=cfg.hidden_size,
                         hidden_dim=cfg.expert_size, dtype=cfg.dtype)
        run = jax.jit(lambda p, h: layer.apply({"params": p}, h)[0])
        ekw = dict(top_k=conf["num_experts_per_tok"],
                   route_scale=conf["route_scale"],
                   first_expert=cfg.moe.first_expert)
        for i in range(cfg.num_dense_layers,
                       0 if dense_only else len(ffn_in)):
            h = ffn_in[i].astype(cfg.dtype)
            p = dict(leaves[i]["moe"])
            p["gate"] = dict(p["gate"], expert_bias=driver.seeded_bias(
                seed, i, p, h))
            out = run(p, h)
            readings = {"sound": rel(out, reference.expert_ffn(p, h, **ekw)),
                        "fp8": rel(out, reference.expert_ffn(
                            p, h, **ekw, operand_bits=FP8))}
            for fault in reference.EXPERT_FAULTS:
                readings[fault] = rel(out, reference.expert_ffn(
                    p, h, **ekw, fault=fault))
            print(json.dumps(dict(seed=seed, what="experts", layer=i,
                                  **readings)), flush=True)
        # the dense layer through the cell's own comparison 3 (it runs
        # ``LlamaBlock._dense_ffn``), each control in the reference's place
        for name, extra in (
                [("sound", {}), ("fp8", {"operand_bits": FP8})]
                + [(f, {"fault": f}) for f in reference.DENSE_FAULTS]):
            said = []
            driver.check_dense(
                types.SimpleNamespace(log=said.append,
                                      check=lambda ok, note: ok),
                cfg, conf, types.SimpleNamespace(
                    layers=reference.layers,
                    dense_ffn=lambda p, h: reference.dense_ffn(p, h, **extra)),
                params, ffn_in)
            print(json.dumps({"seed": seed, "what": "dense", "control": name,
                              "lines": said}), flush=True)
        kinds = list(cfg.kinds)
        for kind in () if dense_only else dict.fromkeys(kinds):
            i = kinds.index(kind)
            module = LlamaAttention(cfg, kind)
            h = attn_in[i].astype(cfg.dtype)
            pos = jnp.arange(h.shape[1])[None, :]
            out = jax.jit(lambda p, h: module.apply({"params": p}, h, pos,
                                                    None))(
                leaves[i]["self_attn"], h)
            akw = dict(n_head=cfg.num_attention_heads, n_kv_head=cfg.kv_heads,
                       head_dim=cfg.head_dim,
                       sliding_window=conf["sliding_window"],
                       rope_theta=conf["rope_theta"],
                       eps=conf["rms_norm_eps"])
            want = lambda **extra: reference.attention(  # noqa: E731
                kind, leaves[i]["self_attn"], h, **akw, **extra)
            readings = {"sound": rel(out, want()),
                        "fp8": rel(out, want(operand_bits=FP8))}
            for fault in reference.FAULTS:
                if applies(reference, fault, kind):
                    readings[fault] = rel(out, want(fault=fault))
            print(json.dumps(dict(seed=seed, what="attention", layer=i,
                                  kind=kind, **readings)), flush=True)
        del engine


if __name__ == "__main__":
    flags = {"--rehearse", "--refuse", "--dense"}
    seeds = [int(a) for a in sys.argv[1:] if a not in flags] or [3000000021]
    rehearse = "--rehearse" in sys.argv
    if "--refuse" not in sys.argv:
        main(seeds, rehearse, "--dense" in sys.argv)
    else:
        wrong = {seed: refusals(seed, rehearse) for seed in seeds}
        print(json.dumps({"what": "refusals", "wrong": wrong}), flush=True)
        if any(wrong.values()) and not rehearse:
            sys.exit(1)
