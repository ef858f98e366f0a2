"""Median ``train/dispatch`` span (ms): the call of the compiled step as
the host sees it: flattening its arguments, the enqueue, and the wait for
a free slot once the host is ``run_ahead`` steps in front of the device
(no signature check since PR 28: the recompile watchdog signs the
arguments only of a call that made an executable)."""
from benchmark.layer_metrics import _program


def read(obs):
    spans = _program.window_spans(obs, "train/dispatch")
    return None if spans is None else _program.median_ms(
        s.dur_s for s in spans)
