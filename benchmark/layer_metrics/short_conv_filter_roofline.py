"""The short convolution's filter's share of its roofline: the least time
the chip could take for the filters the traced steps required (bytes and
operations from shapes, ``benchmark/flops_lfm2.py``: forward and backward,
a remat's second forward does not count) over the device time the filter
took (forward, the second forward and backward: all of what the chip spent
on it).

Where the filter is a kernel of its own, that time is the kernels' in the
window's trace, by the name the configuration's ``trace_names`` gives
(``short_conv_filter``), as ``expert_gemm_roofline`` reads the grouped
matmuls.  Where it is XLA's fusions, which carry no name of the
program's on the v5e, it is the ms a step under the ``short_conv/filter``
scope of ``engine.profile_device_scopes`` (``observed["device_scope_ms"]``,
as ``short_conv_share_pct``).  A driver or a program with neither gives
``None``."""
from benchmark import flops


def read(obs):
    if obs.get("peak") is None \
            or "short_conv_filter_bytes_per_step" not in obs:
        return None
    least, _bound = flops.roofline_seconds(
        obs["short_conv_filter_flops_per_step"],
        obs["short_conv_filter_bytes_per_step"], obs["peak"])
    tr = obs.get("trace")
    name = obs["cell"].config.get("trace_names", {}).get("short_conv_filter")
    if tr is not None and name:
        t = tr.ops_matching(name)
        if t > 0:
            steps = obs["steps"] * tr.window_s / obs["window_s"]
            return 100.0 * least * steps / t
    ms = (obs.get("device_scope_ms") or {}).get("short_conv/filter")
    return 100.0 * least * 1e3 / ms if ms else None
