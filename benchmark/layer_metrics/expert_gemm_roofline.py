"""The grouped expert matmuls' share of their roofline: the least time the
chip could take for the 9 grouped matmuls a layer that the traced steps
required (FLOPs and bytes from shapes, benchmark/flops_moe.py; a remat's
second forward does not count) over the kernels' device time."""
from benchmark import flops_moe


def read(obs):
    tr = obs.get("trace")
    names = obs["cell"].config.get("trace_names", {})
    if tr is None or "expert_gemm" not in names or obs.get("peak") is None \
            or "expert_gemm_flops_per_step" not in obs:
        return None
    t = tr.ops_matching(names["expert_gemm"])
    if t <= 0:
        return None
    steps = obs["steps"] * tr.window_s / obs["window_s"]
    least, _bound = flops_moe.roofline_seconds(
        obs["expert_gemm_flops_per_step"] * steps,
        obs["expert_gemm_bytes_per_step"] * steps, obs["peak"])
    return 100.0 * least / t
