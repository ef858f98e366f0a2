"""Executables JAX built or fetched from its persistent cache inside the
measured window (``backend_compile_duration`` events): shapes the set-up
did not warm, each a stall of the serving loop."""


def read(obs):
    return obs.get("window_compiles")
