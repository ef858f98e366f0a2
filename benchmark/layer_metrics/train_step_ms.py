"""Median host-clock time between consecutive steps becoming ready (ms);
the host runs ``run_ahead_steps`` in front, so this is the device's pace."""
import statistics


def read(obs):
    t = obs.get("step_ready_t") or []
    if len(t) < 3:
        return None
    return statistics.median(b - a for a, b in zip(t, t[1:])) * 1e3
