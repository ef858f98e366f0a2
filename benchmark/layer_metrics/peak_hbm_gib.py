"""What the train step that runs holds on one device while it runs, GiB:
arguments + outputs − the outputs that take a donated argument's buffer +
temporaries, as the compiler booked them for the executable (the program's
gauge ``hbm_exec_reserved_bytes{site="engine.train_step"}``, set once when
the step's executable is made; ``telemetry/memory.py``).  It is what the
allocator refuses by, less the runtime's own ~0.25 GiB: a step whose
reading passes the device's 15.75 GiB does not load.  The result line's
``memory_peak_bytes`` is the allocator's high-water mark and leaves out
most temporaries.  A program without the gauge gives ``None``."""
from benchmark.layer_metrics import _program

GAUGE = "hbm_exec_reserved_bytes"
SITE = "engine.train_step"


def site_gib(obs, gauge, site=SITE):
    """``gauge{site=...}`` of the run's process in GiB; ``None`` without a
    measured window (as every reader of the program's registry) or the
    gauge."""
    if _program.window(obs) is None:
        return None
    try:
        entry = _program.registry_snapshot().get(gauge)
    except ImportError:
        return None
    for sample in (entry or {}).get("samples", ()):
        if sample["labels"].get("site") == site:
            return sample["value"] / 2**30
    return None


def read(obs):
    return site_gib(obs, GAUGE)
