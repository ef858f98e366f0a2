"""The hyper-connections as a share of their roofline: the least time the
chip could take for the bytes and operations the traced steps required
(``benchmark/flops_xing4.py``: each lane read once a pass and written once a
sublayer, forward and backward; a remat's second forward does not count)
over the device time the mechanism took, all of what the chip spent on it.

It reads the same required work whichever implementation runs the
mechanism, as ``kda_roofline`` does: where the passes are kernels of their
own, that time is the kernels' in the window's trace, by the name the
configuration's ``trace_names`` gives (``mhc``); where they are XLA's
fusions, which carry no name of the program's on the v5e, it is the ms a
step under the ``mhc/`` scopes of ``engine.profile_device_scopes``
(``observed["device_scope_ms"]``).  A driver or a program with neither
gives ``None``."""
from benchmark import flops


def read(obs):
    if obs.get("peak") is None or "mhc_bytes_per_step" not in obs:
        return None
    least, _bound = flops.roofline_seconds(
        obs["mhc_flops_per_step"], obs["mhc_bytes_per_step"], obs["peak"])
    tr = obs.get("trace")
    name = obs["cell"].config.get("trace_names", {}).get("mhc")
    if tr is not None and name:
        t = tr.ops_matching(name)
        if t > 0:
            steps = obs["steps"] * tr.window_s / obs["window_s"]
            return 100.0 * least * steps / t
    ms = (obs.get("device_scope_ms") or {}).get("mhc")
    return 100.0 * least * 1e3 / ms if ms else None
