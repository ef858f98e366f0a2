"""Median ``train/dispatch`` span (ms): the call of the compiled step as
the host sees it: the recompile watchdog's signature check, the enqueue,
and the wait for a free slot once the host is ``run_ahead`` steps in
front of the device."""
from benchmark.layer_metrics import _program


def read(obs):
    spans = _program.window_spans(obs, "train/dispatch")
    return None if spans is None else _program.median_ms(
        s.dur_s for s in spans)
