"""The decode tick's share of its roofline: the least time the chip could
take to stream every matmul weight once plus the K/V of the contexts that
were live (time-averaged over the window, from the request records), or to
do the tick's FLOPs, whichever is larger, over the tick's device time."""
from benchmark import flops


def read(obs):
    tr = obs.get("trace")
    if tr is None or not obs.get("decode_ticks") or obs.get("peak") is None:
        return None
    t, runs = tr.modules_matching(obs["decode_module"])
    if not runs:
        return None
    tick_s = t / obs["decode_ticks"]
    c = obs["cell"].config
    live = obs["mean_live_kv_tokens"]
    nbytes = obs["weight_bytes"] + live * obs["kv_bytes_per_token"]
    nflops = flops.decode_tick_flops(c["n_embd"], c["n_layer"],
                                     c["vocab_size"], obs["n_slots"], live)
    least, _bound = flops.roofline_seconds(nflops, nbytes, obs["peak"])
    return 100.0 * least / tick_s
