#!/usr/bin/env python3
"""Does an executable FETCHED from the persistent compile cache still give
``memory_analysis()`` and ``as_text()``?  ``engine.compiled_step()``'s
readers (``telemetry/memory.py``, ``telemetry/device_scopes.py``) rest on
it, and on a staged site's executable having the cache key a plain call's
has.  Three processes on one fresh cache directory: a plain watched
``jax.jit`` call builds, then a ``staged`` site fetches what it built, twice
(``"built"`` absent from its ``executables``); each prints one JSON line.

    chiprun -- python3 scripts/probe_fetched_executable.py
"""
import json
import os
import subprocess
import sys
import tempfile


def child(staged: bool):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.telemetry import (device_scopes, get_registry, memory,
                                         recompile, trace)

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    def step(state, x):
        with trace.device_span("optimizer"):
            w = state["w"] - 0.1 * jnp.tanh(x @ state["w"]).T @ x
        return {"w": w}, jnp.sum(w)

    f = recompile.watch(jax.jit(step, donate_argnums=(0,)),
                        "probe.step", staged=staged)
    state = {"w": jnp.ones((512, 512), jnp.bfloat16)}
    x = jnp.ones((256, 512), jnp.bfloat16)
    for _ in range(2):
        state, _ = f(state, x)
    made = {s["labels"]["how"]: s["value"] for s in get_registry().snapshot()
            ["xla_executables_total"]["samples"]}
    line = {"platform": jax.devices()[0].platform, "staged": staged,
            "executables": made}
    if staged:
        scopes = device_scopes.instruction_scopes(f.compiled)
        line.update(
            memory=memory.memory_breakdown(f.compiled),
            as_text_bytes=len(f.compiled.as_text() or ""),
            instructions_named=len(scopes),
            optimizer_scope=sum("optimizer" in op for op in scopes.values()))
    print(json.dumps(line))


if __name__ == "__main__":
    if "--child" in sys.argv:
        child(staged="--plain" not in sys.argv)
    else:                           # the parent stays off JAX and the chip
        with tempfile.TemporaryDirectory() as cache:
            env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)
            for mode in (["--plain"], [], []):
                subprocess.run([sys.executable, __file__, "--child"] + mode,
                               env=env, check=True)
