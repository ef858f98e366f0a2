"""Required training FLOPs (benchmark/flops.py: 6 x matmul parameters +
causal attention forward and backward, no recomputation) a second, over
chips x the chip's published bf16 peak, in percent."""


def read(obs):
    if "flops_per_token" not in obs or obs.get("peak") is None:
        return None
    rate = obs["flops_per_token"] * obs["tokens"] / obs["window_s"]
    return 100.0 * rate / (obs["n_devices"] * obs["peak"]["bf16_flops_per_s"])
