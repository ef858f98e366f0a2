"""What the probes of a share's cell share (Mellum 2's three, Trinity's
two): the cell, ``train-mellum2-8k-1chip`` unless named, as the benchmark
loads it, and the trainer built on it as the cell's driver builds it (weights from the seed, the file's ``init_scale``, the packed
batches), with the configuration edited first where a probe varies it."""
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "train-mellum2-8k-1chip"


def build(seed: int, rehearse: bool = False, edit=None, init_scale=None,
          cell: str = CELL):
    """``(cell, driver, engine, cfg, conf, batches)``; ``edit(conf)`` may
    change the sized configuration before the engine is built, and
    ``rehearse`` takes the file's CPU sizes (control flow only)."""
    from benchmark import loadgen
    from benchmark.harness import manifest as M
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = M.load_cell(M.load_manifest(ROOT), cell, ROOT)

    def sized(section):
        out = {k: v for k, v in section.items() if k != "rehearse"}
        if rehearse:
            out.update(section.get("rehearse", {}))
        return out

    conf = sized(cell.config)
    if edit is not None:
        edit(conf)
    cell.config = dict(conf, rehearse={})       # already sized
    ctx = types.SimpleNamespace(seed=seed, cell=cell, rehearse=rehearse,
                                sized=lambda sec: {k: v for k, v in sec.items()
                                                   if k != "rehearse"})
    driver = cell.driver()
    train_lm = getattr(driver, "train_lm", driver)      # OLMoE's is it
    engine, cfg, _ = train_lm.build(ctx)
    engine.init_params()
    factors = conf.get("init_scale", {}) if init_scale is None \
        else {"embed_tokens": init_scale}
    if factors:                     # the XL cell's driver has no such step
        train_lm.scale_init(engine, factors)
    batches = loadgen.packed_batches(sized(cell.traffic), seed,
                                     engine.train_batch_size, cfg.vocab_size)
    return cell, driver, engine, cfg, conf, batches
