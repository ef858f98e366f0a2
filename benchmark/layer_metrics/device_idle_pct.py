"""1 - (union of device operation intervals / traced window), percent."""


def read(obs):
    tr = obs.get("trace")
    return None if tr is None else 100.0 * (1.0 - tr.busy_s / tr.window_s)
