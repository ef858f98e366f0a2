"""Seconds the process spent tracing Python into jaxprs and lowering them
to MLIR, process start to now (the program's
``xla_compile_seconds_total``, phases ``trace`` + ``lower``, every span
but ``init/params``): the eval step, the train step and the eager
programs around them; the part of set-up no compile cache removes."""
from benchmark.layer_metrics import _program


def read(obs):
    return _program.compile_seconds(obs, ("trace", "lower"))
