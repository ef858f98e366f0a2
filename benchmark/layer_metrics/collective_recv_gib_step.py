"""What ONE device receives over the links in one train step, GiB: the sum
over ``op`` of the program's gauge ``step_collective_recv_bytes{site=
"engine.train_step", op}``, booked once when the step's executable is made
from the collectives of its optimized HLO (``telemetry/device_scopes.py``
``collective_ledger``: an all-gather's result x (n-1)/n, a reduce-scatter's
x (n-1), an all-reduce's 2 x (n-1)/n, a permute's result).  The same to the
byte in every run of one commit.  ``by_op`` gives the parts.  A program
without the gauge (or an executable of one device, which books none) gives
``None``."""
from benchmark.layer_metrics import _program

GAUGE = "step_collective_recv_bytes"
SITE = "engine.train_step"


def by_op(obs, gauge=GAUGE, site=SITE):
    """``{op: value}`` of ``gauge{site=..., op}`` in the run's process;
    ``None`` without a measured window or the gauge."""
    if _program.window(obs) is None:
        return None
    try:
        entry = _program.registry_snapshot().get(gauge)
    except ImportError:
        return None
    found = {s["labels"]["op"]: s["value"]
             for s in (entry or {}).get("samples", ())
             if s["labels"].get("site") == site}
    return found or None


def read(obs):
    found = by_op(obs)
    return None if found is None else sum(found.values()) / 2**30
