"""The full layers' (full causal attention) flash kernels' share of their
roofline: the kernels named ``trace_names.flash_full`` against the causal
attention those layers required (``_flash_by_kind.py``)."""
from benchmark.layer_metrics import _flash_by_kind


def read(obs):
    return _flash_by_kind.roofline(obs, "flash_full", "full_attention")
