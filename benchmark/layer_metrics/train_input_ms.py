"""Median per step of the input path inside ``train_batch`` (ms):
``train/next-batch`` (pull from the iterator, concatenate, relayout) plus
``train/device-put``, summed over the children of each ``train/step``
span that started inside the window."""
from benchmark.layer_metrics import _program

INPUT_SPANS = ("train/next-batch", "train/device-put")


def read(obs):
    steps = _program.window_spans(obs, "train/step")
    if not steps:
        return None
    per_step = {s.id: 0.0 for s in steps}
    for s in _program.tracer().spans(prefix="train/", since_s=steps[0].start_s,
                                     until_s=steps[-1].end_s):
        if s.name in INPUT_SPANS and s.parent_id in per_step:
            per_step[s.parent_id] += s.dur_s
    return _program.median_ms(per_step.values())
