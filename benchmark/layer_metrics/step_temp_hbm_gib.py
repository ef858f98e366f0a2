"""The temporaries of the train step that runs, GiB: activations saved
for the backward, recomputation buffers, kernel scratch: the part of
``peak_hbm_gib`` that a kernel, a remat policy or a batch size moves (the
program's gauge ``hbm_exec_temp_bytes{site="engine.train_step"}``).  A
program without the gauge gives ``None``."""
from benchmark.layer_metrics import peak_hbm_gib

GAUGE = "hbm_exec_temp_bytes"


def read(obs):
    return peak_hbm_gib.site_gib(obs, GAUGE)
