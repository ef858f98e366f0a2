"""The mean pass at which a token leaves a looped stack under the learned
exit distribution, ``sum_t t p_t`` over the labelled tokens of the last
finished step (1 is the first pass): 1.875 at the initial gates of 1/2 and
four passes; 4.0 is a gate shut, 1.0 a gate that lets no token past the
first pass.

Read from the program's gauge ``ut_exit_step_mean`` (``models/llama.py
record_step_stats``).  A program without it (one pass, or a commit from
before it) gives ``None``."""

GAUGE = "ut_exit_step_mean"


def read(obs):
    try:
        from deepspeed_tpu.telemetry import get_registry
    except ImportError:
        return None
    entry = get_registry().snapshot().get(GAUGE)
    if not entry or not entry["samples"]:
        return None
    return entry["samples"][0]["value"]
