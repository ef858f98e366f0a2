"""What ``flash_window_roofline`` and ``flash_full_roofline`` share: the
roofline share of the flash kernels of ONE layer type.  The least time the
chip could take for the attention those layers required in the traced steps
(FLOPs over the keys each query keeps and bytes with keys and values at
their own heads, from shapes: the configuration's ``flops`` module) over
the device time of the kernels the configuration names for that type in
``trace_names``.  A configuration without that name, or whose flops module
does not count a layer type alone, gives ``None``."""
import importlib


def roofline(obs, trace_name: str, kind: str):
    tr = obs.get("trace")
    conf = obs["cell"].config
    name = conf.get("trace_names", {}).get(trace_name)
    if tr is None or name is None or obs.get("peak") is None \
            or "flops" not in conf or "seq_len" not in obs["cell"].traffic:
        return None
    flops = importlib.import_module("benchmark." + conf["flops"])
    if not hasattr(flops, "attention_flops_per_token"):
        return None
    t = tr.ops_matching(name)
    if t <= 0:
        return None
    seq = int(obs["cell"].traffic["seq_len"])
    per_dev = obs["tokens"] / obs["n_devices"] * tr.window_s / obs["window_s"]
    least, _bound = flops.roofline_seconds(
        flops.attention_flops_per_token(conf, seq, 3, kind) * per_dev,
        flops.flash_train_bytes_per_token(conf, kind=kind) * per_dev,
        obs["peak"])
    return 100.0 * least / t
