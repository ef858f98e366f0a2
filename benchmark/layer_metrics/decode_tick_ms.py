"""Device time of the decode-window executable over the ticks it ran in
the traced window (ms a tick)."""


def read(obs):
    tr = obs.get("trace")
    if tr is None or not obs.get("decode_ticks"):
        return None
    t, runs = tr.modules_matching(obs["decode_module"])
    return 1e3 * t / obs["decode_ticks"] if runs else None
