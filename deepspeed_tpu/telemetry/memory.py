"""Per-executable + live-array HBM accounting.

OOM headroom on TPU is invisible until the allocator throws: the
compiled program's reservation is decided at compile time
(``compiled.memory_analysis()``) and the rest of HBM is whatever arrays
the host still holds alive.  This module turns both into scrapeable
gauges:

- :func:`memory_breakdown` — THE one normalizer over
  ``compiled.memory_analysis()`` (``profiling/flops_profiler.py`` and
  ``autotuning/autotuner.py`` previously each had a private copy).
  Bytes are PER-DEVICE: XLA analyzes the post-SPMD-partitioning
  program, so the numbers compare against one chip's HBM directly —
  no further division (see ``autotuner.py`` trial-fit logic).
- :func:`record_compiled` — publish a breakdown as
  ``hbm_exec_{args,output,alias,temp,generated_code,reserved,total}_bytes
  {site=...}`` gauges (and ``hbm_exec_peak_bytes`` where the backend
  reports a peak).  Wired where an executable is made: the recompile
  watchdog books every executable a ``staged`` site makes
  (``telemetry/recompile.py``: the training engine's steps, so the step
  that runs is booked on its first call and ``engine.
  record_memory_profile()`` only reads it back), and serving's
  ``warmup_windows`` / ``_warmup_admission`` book their AOT handles.
- :func:`sample_live_hbm` — ``live_hbm_bytes`` (max per-device bytes
  pinned by live ``jax.Array``\\ s) + allocator stats where the backend
  exposes them; registered as a scrape-time collector so ``/metrics``
  always serves a fresh reading.
"""
from __future__ import annotations

from typing import Optional

from . import registry as _registry

__all__ = ["memory_breakdown", "peak_bytes", "record_compiled",
           "per_device_shard_bytes", "sample_live_hbm", "tree_bytes"]

# (gauge suffix, CompiledMemoryStats attribute)
_FIELDS = (
    ("args", "argument_size_in_bytes"),
    ("output", "output_size_in_bytes"),
    ("alias", "alias_size_in_bytes"),
    ("temp", "temp_size_in_bytes"),
    ("generated_code", "generated_code_size_in_bytes"),
    ("peak", "peak_memory_in_bytes"),
)


def memory_breakdown(compiled) -> Optional[dict]:
    """Normalized per-device byte breakdown of a compiled executable.

    Returns ``{"args": .., "output": .., "alias": .., "temp": ..,
    "generated_code": .., "reserved": .., "total": ..}`` (floats, bytes;
    ``"peak"`` too where the backend reports one that is not 0) or None
    when the backend exposes no analysis.  ``reserved`` = args + output −
    alias + temp is what the program holds on the device while it runs:
    an output that takes a donated argument's buffer (``alias``) is one
    buffer, not two.  ``total`` = args + output + temp is the same for a
    program that donates nothing, which is what the autotuner's and the
    flops profiler's fit checks lower; on a step that donates its state
    it counts the state twice (generated code lives in its own arena and
    is reported separately).
    """
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if isinstance(ma, (list, tuple)):            # some backends: [stats]
        ma = ma[0] if ma else None
    if ma is None:
        return None
    out = {key: float(getattr(ma, attr, 0) or 0) for key, attr in _FIELDS}
    if not out["peak"]:
        del out["peak"]
    out["total"] = out["args"] + out["output"] + out["temp"]
    out["reserved"] = out["total"] - out["alias"]
    return out


def peak_bytes(compiled) -> float:
    """Per-device working-set bytes of ``compiled`` (NaN when the
    backend exposes no analysis) — the autotuner's HBM-fit number."""
    bd = memory_breakdown(compiled)
    return bd["total"] if bd is not None else float("nan")


def record_compiled(compiled, site: str,
                    registry: Optional[_registry.Registry] = None
                    ) -> Optional[dict]:
    """Publish ``compiled``'s breakdown as per-site HBM gauges; returns
    the breakdown (None when unavailable — nothing is published)."""
    bd = memory_breakdown(compiled)
    if bd is None:
        return None
    reg = registry or _registry.get_registry()
    for key, value in bd.items():
        reg.gauge(
            f"hbm_exec_{key}_bytes",
            f"per-device {key} bytes of the compiled executable",
            labelnames=("site",)).labels(site=site).set(value)
    return bd


def tree_bytes(tree) -> int:
    """Total GLOBAL bytes of every leaf in ``tree`` — works on real
    arrays and on ``ShapeDtypeStruct`` trees alike, so the same
    arithmetic sizes a page budget from an abstract cache tree
    (``inference/kvreuse.py``) and meters live parked prefill caches
    (``serving_parked_bytes``).  Logical bytes, not per-device shards —
    use :func:`per_device_shard_bytes` for residency."""
    import math

    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += math.prod(leaf.shape) * np.dtype(leaf.dtype).itemsize
    return int(total)


def per_device_shard_bytes(arrays) -> tuple:
    """``({device: resident bytes}, n_arrays)`` over ``arrays``' local
    shards — THE accumulation shared by the live-array sampler and the
    inference params gauge.  Arrays that fail to expose shards (deleted
    or donated between listing and reading) are skipped, not fatal."""
    per_dev: dict = {}
    n = 0
    for arr in arrays:
        n += 1
        try:
            for shard in arr.addressable_shards:
                d = shard.device
                per_dev[d] = per_dev.get(d, 0) + (
                    shard.data.nbytes if shard.data is not None else 0)
        except Exception:
            continue
    return per_dev, n


def sample_live_hbm(registry: Optional[_registry.Registry] = None) -> dict:
    """Refresh the live-memory gauges; returns what was published.

    - ``live_hbm_bytes``: max over local devices of bytes pinned by live
      ``jax.Array`` shards (the committed side of OOM headroom);
    - ``live_hbm_arrays``: how many live arrays pin them;
    - ``hbm_device_in_use_bytes`` / ``hbm_device_limit_bytes``: the
      allocator's own view where the backend exposes ``memory_stats()``
      (TPU does; CPU usually returns nothing).

    Registered as a collector (:func:`registry.register_collector`), so
    every ``/metrics`` scrape and exit dump reads fresh values; also
    callable directly.  Cost is a walk of the live-array table — fine at
    scrape cadence, not for inner loops.
    """
    import sys

    jax = sys.modules.get("jax")    # never force jax in from a collector
    if jax is None:
        return {}
    reg = registry or _registry.get_registry()
    out: dict = {}
    try:
        per_dev, n = per_device_shard_bytes(jax.live_arrays())
        live = max(per_dev.values(), default=0)
        reg.gauge("live_hbm_bytes",
                  "max per-device bytes pinned by live jax arrays"
                  ).set(float(live))
        reg.gauge("live_hbm_arrays", "live jax arrays").set(float(n))
        out["live_hbm_bytes"] = float(live)
        out["live_hbm_arrays"] = float(n)
    except Exception:
        pass
    try:
        stats = jax.local_devices()[0].memory_stats() or {}

        # literal names at the call sites so DSTPU006 sees the
        # declarations; only declared when the backend reports the stat
        # (CPU's memory_stats() is empty)
        def gauge(name, src, desc):
            if src in stats:
                reg.gauge(name, desc).set(float(stats[src]))
                out[name] = float(stats[src])

        gauge("hbm_device_in_use_bytes", "bytes_in_use",
              "allocator bytes_in_use on device 0")
        gauge("hbm_device_peak_bytes", "peak_bytes_in_use",
              "allocator peak_bytes_in_use on device 0")
        gauge("hbm_device_limit_bytes", "bytes_limit",
              "allocator bytes_limit on device 0")
    except Exception:
        pass
    return out
