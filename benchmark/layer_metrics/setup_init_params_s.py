"""Seconds inside ``engine.init_params`` (the program's ``init/params``
span, cumulative): weights made on the device from the seed, with the
trace, lowering and compile or fetch of the init program, which the two
compile metrics therefore leave out."""
from benchmark.layer_metrics import _program


def read(obs):
    tracer = _program.tracer()
    if _program.window(obs) is None or tracer is None:
        return None
    total = tracer.totals().get(_program.INIT_SPAN)
    return None if total is None else total["seconds"]
