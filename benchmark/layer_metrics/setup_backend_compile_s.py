"""Seconds the process spent in the XLA compiler or fetching executables
from the persistent cache, process start to now (the program's
``xla_compile_seconds_total``, phases ``backend`` + ``fetch``, every span
but ``init/params``): the part of set-up a warm cache shrinks."""
from benchmark.layer_metrics import _program


def read(obs):
    return _program.compile_seconds(obs, ("backend", "fetch"))
