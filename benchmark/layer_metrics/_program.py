"""What the readers of ``program_span`` and ``program_counter`` metrics
share: the program's own tracer and registry, read in the run's process.

The program (``deepspeed_tpu.telemetry.trace``) keeps every finished host
span in a ring, on the ``time.perf_counter()`` axis the drivers use for
``step_ready_t``, and books compile seconds by phase in a registry
counter.  A program without them (a parent commit from before the tracer
kept a ring) gives ``None`` everywhere here: the metric is then left out
of the line, it is not an error.
"""
import statistics

COMPILE_SECONDS = "xla_compile_seconds_total"
INIT_SPAN = "init/params"      # its seconds, compiles included, are a metric of their own


def tracer():
    """The program's tracer if it keeps a readable ring, else ``None``."""
    try:
        from deepspeed_tpu.telemetry import trace
    except ImportError:
        return None
    return trace if hasattr(trace, "spans") and hasattr(trace, "totals") \
        else None


def registry_snapshot():
    from deepspeed_tpu.telemetry import get_registry

    return get_registry().snapshot()


def window(obs):
    """``(first, last)`` step-ready times of the measured window, or
    ``None`` when the driver recorded fewer than two."""
    t = obs.get("step_ready_t") or []
    return (t[0], t[-1]) if len(t) >= 2 else None


def window_spans(obs, prefix):
    """Spans named ``prefix...`` that STARTED inside the window, oldest
    first; ``None`` without a window or a ring."""
    win, tr = window(obs), tracer()
    if win is None or tr is None:
        return None
    return tr.spans(prefix=prefix, since_s=win[0], until_s=win[1])


def median_ms(durations_s):
    durations_s = list(durations_s)
    return statistics.median(durations_s) * 1e3 if durations_s else None


def compile_seconds(obs, phases):
    """Seconds of the given compile phases, process start to now, booked
    under every span but ``init/params`` (``setup_init_params_s`` has
    those, so the three set-up metrics are disjoint parts of ``setup_s``);
    ``None`` without a window or the counter."""
    if window(obs) is None or tracer() is None:
        return None
    entry = registry_snapshot().get(COMPILE_SECONDS)
    if not entry:
        return None
    return sum(s["value"] for s in entry["samples"]
               if s["labels"].get("phase") in phases
               and s["labels"].get("span") != INIT_SPAN)
