#!/usr/bin/env python3
"""Read, on the chip and at the timed sizes, what each tolerance of
``benchmark/configs/keye-vl2-30b-a3b-z3-8bit.json`` ``reference_check`` must
refuse: the plain reference with both operands of every matrix
multiplication rounded to float8 e4m3, and the layer computed wrong in the
named ways (``reference.FAULTS``: the selection ignored, top-k over
non-causal keys, half as many keys, the indexer's ReLU, head weights, key
LayerNorm or rotary left out, the indexer's loss over all causal keys,
key-value head ``h % 4``), each against what the PROGRAM computes on the
same weights and row, through the cell's own readers
(``benchmark/drivers/train_keye.py read_*``).  Prints one line a reading;
the numbers go into the configuration file's ``*_reason`` and PERF.md by
hand.

    chiprun -- python3 scripts/keye_tolerances.py [--refuse] [--loss] [seed ...]

``--loss`` also reads the whole forward's loss under float8 and under the
loss's own fault (two more reference forwards).  ``--refuse`` then holds the
limits that were written to those readings: every reading of a sound
reference has to lie inside its limit, every named fault outside the limit
of a comparison that is there to see it, and float8 outside at least one;
exit code 1 where one does not.  ``--rehearse`` runs the same control flow
at the configuration's CPU sizes (where the limits mean nothing: no exit
code).
"""
import json
import sys

from mellum2_cell import build as _build

CELL = "train-keye-dsa-32k-1chip"
FP8 = (4, 3)
# which comparisons have to refuse which fault ("layer loss": the first
# layer's indexer loss alone)
SEEN_BY = {"dense": ("core",), "kv_mod": ("core", "layer"),
           "loss_all_causal": ("layer loss",),
           "noncausal_topk": ("selection",), "half_topk": ("selection",),
           "no_relu": ("scores", "selection"), "no_w": ("scores", "selection"),
           "no_key_norm": ("scores", "selection"),
           "no_indexer_rope": ("scores", "selection")}
# the core's comparison of the indexer's gradients has to refuse these
# itself, whatever else does
BY_INDEXER_GRADS = ("loss_all_causal", "no_relu")
INDEXER_GRADS = tuple(own + name for own in ("", "own ")
                      for name in ("dqI", "dkI", "dw"))


def readings(seed, rehearse, with_loss):
    """The comparisons that came out wrong for one seed."""
    cell, driver, engine, cfg, conf, batches = _build(seed, rehearse,
                                                      cell=CELL)
    reference = cell.reference()
    params = engine.state.params
    kw = driver.reference_kwargs(conf)
    tol = conf["reference_check"]
    topk = kw["topk"]
    ids = next(batches)["input_ids"][:1]
    out = {}

    def say(what, **r):
        out.setdefault(what, {}).update(r)
        print(json.dumps(dict(seed=seed, what=what, **r)), flush=True)

    ffn_in, attn_in = [], []
    got, p_ce, rest = driver.program_losses(engine, ids)

    def against(ce, aux, idx):
        return {"loss": got - ce - aux - idx, "ce": p_ce - ce,
                "indexer": rest - aux - idx}

    def parts(**extra):
        return against(*(float(x) for x in reference.loss_parts(
            params, ids, **kw, **extra)))

    ce, aux, idx = (float(x) for x in reference.loss_parts(
        params, ids, **kw, ffn_inputs=ffn_in, attn_inputs=attn_in))
    say("loss", engine=got, sound=against(ce, aux, idx),
        reference={"ce": ce, "aux": aux, "indexer": idx})
    if with_loss:
        say("loss", fp8=parts(operand_bits=FP8),
            loss_all_causal=parts(fault="loss_all_causal"))

    def experts(**extra):
        import types

        from benchmark.drivers import train_mellum2

        said = []
        train_mellum2.check_experts(
            types.SimpleNamespace(log=said.append, check=lambda ok, note: ok),
            cfg, conf, types.SimpleNamespace(
                layers=reference.layers,
                expert_ffn=lambda p, h, **k: reference.expert_ffn(
                    p, h, **k, **extra)), params, ffn_in)
        return [float(x) for x in said[0].split("a layer ", 1)[1].split()]

    say("experts", sound=experts(), fp8=experts(operand_bits=FP8))
    h = attn_in[0].astype(cfg.dtype)
    del ffn_in, attn_in
    p_attn = next(iter(reference.layers(params, 1)))["self_attn"]
    mine = driver.program_indexer(cfg, p_attn, h)
    rows = min(int(tol["check_rows"]), h.shape[1])

    def theirs(**extra):
        t = reference.indexer(p_attn, h, **kw, **extra)
        return t + (reference.selection(*t, topk=topk, **extra),)

    controls = [("sound", {}), ("fp8", {"operand_bits": FP8})]
    for name, extra in controls + [(f, {"fault": f})
                                   for f in reference.SELECTION_FAULTS]:
        # a faulty indexer's operands, scored and selected as the fault says
        score_fault = {k: v for k, v in extra.items()
                       if k == "operand_bits" or v == "no_relu"}
        t = theirs(**extra)
        faulty = type(reference)("faulty")
        faulty.indexer_scores = lambda *a, **k: reference.indexer_scores(
            *a, **k, **score_fault)
        r = driver.read_indexer(cfg, faulty, mine, t, topk, rows)
        say("scores", **{name: r["scores"]})
        say("selection", **{name: r["overlap"]})
    sound = theirs()
    qkv = reference.qkv(p_attn, h, **kw)
    # no_relu here: the reference's selection stands, its scores under the
    # softmax of the indexer's loss are the fault's
    for name, extra in controls + [(f, {"fault": f}) for f in (
            "dense", "kv_mod") + BY_INDEXER_GRADS]:
        r = driver.read_core(cfg, reference, qkv, mine, sound, topk, seed,
                             **extra)
        say("core", **{name: r})
        say("indexer grads", **{name: {n: r[n] for n in INDEXER_GRADS}})
    for name, extra in controls + [(f, {"fault": f}) for f in
                                   ("kv_mod", "loss_all_causal", "dense")]:
        r = driver.read_layer(cfg, reference, p_attn, h, kw, **extra)
        say("layer", **{name: r["out"]})
        say("layer loss", **{name: r["indexer_loss"]})
    del engine

    def over(what, r):
        """Whether reading ``r`` of comparison ``what`` is outside its
        limit."""
        if what == "loss":
            return (abs(r["loss"]) > tol["loss_abs_tol"]
                    or abs(r["ce"]) > tol["ce_abs_tol"]
                    or abs(r["indexer"]) > tol["indexer_loss_abs_tol"])
        if what == "experts":
            return max(r) > tol["expert_rel_tol"]
        if what == "scores":
            return r > tol["indexer_score_rel_tol"]
        if what == "selection":
            return r < tol["selection_overlap_floor"]
        if what == "layer":
            return r > tol["attention_rel_tol"]
        if what == "layer loss":
            return abs(r) > tol["layer_indexer_loss_abs_tol"]
        return any(not abs(v) <= driver.core_limit(tol, n)
                   for n, v in r.items())

    wrong = [f"{what} sound" for what, r in out.items()
             if over(what, r["sound"])]
    for fault, seers in SEEN_BY.items():
        if not any(over(what, out[what][fault]) for what in seers
                   if fault in out[what]):
            wrong.append(f"{fault} passes {seers}")
    wrong += [f"{fault} passes the indexer's gradients"
              for fault in BY_INDEXER_GRADS
              if not over("indexer grads", out["indexer grads"][fault])]
    if not any(over(what, r["fp8"]) for what, r in out.items()
               if "fp8" in r):
        wrong.append("fp8 passes every limit")
    return wrong


if __name__ == "__main__":
    flags = {"--rehearse", "--refuse", "--loss"}
    seeds = [int(a) for a in sys.argv[1:] if a not in flags] or [3000000021]
    rehearse = "--rehearse" in sys.argv
    wrong = {seed: readings(seed, rehearse, "--loss" in sys.argv)
             for seed in seeds}
    print(json.dumps({"what": "refusals", "wrong": wrong}), flush=True)
    if "--refuse" in sys.argv and any(wrong.values()) and not rehearse:
        sys.exit(1)
