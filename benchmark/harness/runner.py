"""One run of one cell: set-up, the measured window, the result line.

``run.py`` calls :func:`main`.  The driver named by the cell's
configuration does the work against the program; this module owns the
clock for ``setup_s``, the device check, the compile counter, the profiler
trace and the one JSON line the run ends with.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

from . import manifest as manifest_mod

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")
# a rehearsal (CPU, tiny sizes) proves control flow only: it prints this
# line instead of the result line, and no device metric
REHEARSAL_KEY = "rehearsal"


class Context:
    """What a driver is handed: the cell, the seed, the clock and the
    hooks the harness owns (compile counter, trace, host spans)."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 rehearse: bool, peak: Optional[dict], t_process: float):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.rehearse = bool(rehearse)
        self.peak = peak
        self.t_process = t_process
        self.compiles = 0               # executables built or fetched so far
        self.notes: List[str] = []      # why `correct` is false, if it is
        self.trace_dir = os.path.join(cell.root, ".bench_out", "trace",
                                      cell.name)
        self._tracing = False

    # -- sizes ---------------------------------------------------------
    def sized(self, section: dict) -> dict:
        """A configuration section with its ``rehearse`` overrides applied
        when (and only when) this is a rehearsal."""
        out = {k: v for k, v in section.items() if k != "rehearse"}
        if self.rehearse:
            out.update(section.get("rehearse", {}))
        return out

    # -- correctness ---------------------------------------------------
    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.notes.append(what)
            print(f"[check failed] {what}", file=sys.stderr, flush=True)
        return bool(ok)

    def log(self, msg: str) -> None:
        print(f"[{time.perf_counter() - self.t_process:7.1f}s] {msg}",
              file=sys.stderr, flush=True)

    # -- host spans and the trace -------------------------------------
    def span(self, name: str):
        """A host span in the profiler's own trace (only while tracing:
        an annotation outside a trace costs a no-op call)."""
        if not self._tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench/" + name)

    @property
    def window_seconds(self) -> float:
        """A traced run measures a shorter window, whole under the trace:
        traces are large and stopping one stalls the host for seconds."""
        if self.trace:
            return min(self.seconds,
                       float(self.cell.traffic.get("trace_seconds", 10)))
        return self.seconds

    def start_trace(self) -> None:
        if not self.trace:
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans are ours alone
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._tracing = True

    def stop_trace(self) -> None:
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False

    def step_scopes(self, engine) -> dict:
        """``{instruction name: op_name}`` of the train step that ran, by
        which a traced run's ``breakdown`` names XLA's fusions
        (``trace_reduce.booked_times``).  The program parses the executable's
        text for it, once an executable, so a driver asks after the window,
        when ``setup_s`` is closed; an untraced run never asks."""
        if not self.trace:
            return {}
        from deepspeed_tpu.telemetry.device_scopes import instruction_scopes

        t0 = time.perf_counter()
        scopes = instruction_scopes(engine.compiled_step())
        self.log(f"the step's {len(scopes)} instructions by op_name, for the "
                 f"breakdown's names: {time.perf_counter() - t0:.2f}s")
        return scopes


def count_compiles(ctx: Context) -> None:
    """From now on, count every executable JAX builds or fetches from its
    persistent cache (both raise ``backend_compile_duration``)."""
    import jax

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            ctx.compiles += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def device_or_exit(cell, rehearse: bool):
    """The devices of this run, or a non-zero exit with no result line."""
    import jax

    devs = jax.devices()
    if rehearse:
        return devs, None
    peaks = manifest_mod.load_peaks(cell.root)
    dev = devs[0]
    if dev.platform != "tpu":
        sys.exit(f"benchmark: JAX found no TPU (platform {dev.platform!r}); "
                 f"a cell is measured on the chip or not at all "
                 f"(--rehearse checks control flow on the CPU)")
    if len(devs) < cell.chips:
        sys.exit(f"benchmark: cell {cell.name} needs {cell.chips} chips, "
                 f"JAX found {len(devs)}")
    if dev.device_kind not in peaks:
        sys.exit(f"benchmark: no peaks for device kind {dev.device_kind!r} "
                 f"in benchmark/peaks.json; add the chip, do not default")
    return devs, peaks[dev.device_kind]


def result_line(ctx: Context, out: dict, devices, which: str,
                trace_summary=None) -> dict:
    """The contract's result object: end-to-end metrics (``--trace 0``) or
    per-layer metrics (``--trace 1``)."""
    dev = devices[0]
    peak_mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_mem}
    metrics: Dict[str, dict] = {}
    if which == "end_to_end":
        values = dict(out["end_to_end"])
        values["setup_s"] = out["setup_s"]
        for m in ctx.cell.end_to_end:
            if values.get(m["name"]) is None:
                ctx.check(False, f"end-to-end metric {m['name']} has no value")
                continue
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    else:
        obs = dict(out["observed"], trace=trace_summary, cell=ctx.cell,
                   peak=ctx.peak, window_s=out["window_s"])
        for m in ctx.cell.per_layer:
            value = ctx.cell.reader(m["name"])(obs)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = trace_summary.busy_s
        device["window_s"] = trace_summary.window_s
    line = {"correct": not ctx.notes, "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics, "device": device}
    if trace_summary is not None:
        line["breakdown"] = trace_summary.breakdown()
    # what a driver says of the run in words (which implementation ran a
    # mechanism the cell holds by other means)
    said = {k: v for k, v in out["observed"].items() if isinstance(v, str)}
    if said:
        line["said"] = said
    if ctx.notes:
        line["notes"] = ctx.notes
    return line


def main(argv=None, t_process: Optional[float] = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, control flow only: prints a "
                         "'rehearsal' line and no result line")
    args = ap.parse_args(argv)
    root = manifest_mod.ROOT
    manifest = manifest_mod.load_manifest(root)
    cell = manifest_mod.load_cell(manifest, args.workload, root)
    seconds = float(manifest["run_seconds"] if args.seconds is None
                    else args.seconds)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={cell.chips}"
            ).strip()
    sys.path.insert(0, root)
    devices, peak = device_or_exit(cell, args.rehearse)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ctx = Context(cell, args.seed, seconds, bool(args.trace), args.rehearse,
                  peak, t_process)
    ctx.log(f"cell {cell.name} seed {args.seed} seconds {seconds} "
            f"trace {args.trace} on {len(devices)} x {devices[0].device_kind}")
    count_compiles(ctx)
    try:
        out = cell.driver().run(ctx, cell.reference())
    finally:
        ctx.stop_trace()
    summary = None
    if args.trace:
        from .. import trace_reduce

        path = trace_reduce.find_xplane(ctx.trace_dir)
        if args.rehearse:
            # a CPU trace has no device plane; nothing is reduced from it
            ctx.log(f"rehearsal trace written: {os.path.getsize(path)} bytes")
        else:
            summary = trace_reduce.summarize(
                *trace_reduce.read_xplane(path),
                scopes=out["observed"].get("instruction_scopes"))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    if args.rehearse:
        print(json.dumps({REHEARSAL_KEY: True, "workload": cell.name,
                          "correct": not ctx.notes, "notes": ctx.notes,
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "compiles_in_window": out["compiles_in_window"],
                          "counts": out.get("counts", {})}), flush=True)
        return 0 if not ctx.notes else 1
    line = result_line(ctx, out, devices,
                       "per_layer" if args.trace else "end_to_end", summary)
    print(json.dumps(line), flush=True)
    return 0
