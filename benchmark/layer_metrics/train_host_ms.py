"""Median host time of one ``engine.train_batch`` call (ms): the program's
``train/step`` spans that started inside the window.  The device's pace is
``train_step_ms``; this is how much of it the host is busy or blocked in
the call (dispatch blocks once the host runs ahead of the device)."""
from benchmark.layer_metrics import _program


def read(obs):
    steps = _program.window_spans(obs, "train/step")
    return None if steps is None else _program.median_ms(
        s.dur_s for s in steps)
