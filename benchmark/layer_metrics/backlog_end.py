"""Requests queued or parked (prefilled, waiting for a slot) when the
window closed."""


def read(obs):
    return obs.get("backlog_end")
