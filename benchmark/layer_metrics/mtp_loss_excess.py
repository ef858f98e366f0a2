"""How much harder the second head's task still is than the first's:
``mtp_loss{depth="1"} - lm_loss`` at the last finished step, in nats.  The
prediction block reads the model's own table and head one token further
ahead, so it starts near the main loss and stays a little above it while
both fall; a block that is skipped books no gauge (``None``), and one fed
the wrong labels reads the table's entropy against a main loss that has
moved on.

Read from the program's gauges (``models/llama.py record_step_stats``).  A
program without them (no prediction block, or a commit from before it)
gives ``None``."""

MAIN, SECOND = "lm_loss", "mtp_loss"


def read(obs):
    try:
        from deepspeed_tpu.telemetry import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()
    main, second = snap.get(MAIN), snap.get(SECOND)
    if not main or not second or not main["samples"]:
        return None
    depth1 = [s["value"] for s in second["samples"]
              if s["labels"].get("depth") == "1"]
    if not depth1:
        return None
    return depth1[0] - main["samples"][0]["value"]
