"""How far a hyper-connection's lane-to-lane map is from doubly stochastic
after its Sinkhorn sweeps: the largest ``|row sum - 1|`` or ``|column sum -
1|`` over the tokens of the last finished step, the worst of the blocks
(the prediction block's among them).  ~1e-5 at the near-identity start; it
grows with the spread of the map's logits, and a value near 1 says the
sweeps no longer reach the manifold (the stream's scale then drifts from
layer to layer).

Read from the program's gauge ``mhc_res_marginal_err{layer}``
(``models/llama.py record_step_stats``).  A program without it (one lane,
or a commit from before it) gives ``None``."""

GAUGE = "mhc_res_marginal_err"


def read(obs):
    try:
        from deepspeed_tpu.telemetry import get_registry
    except ImportError:
        return None
    entry = get_registry().snapshot().get(GAUGE)
    if not entry or not entry["samples"]:
        return None
    return max(s["value"] for s in entry["samples"])
