"""The sliding layers' (window attention) flash kernels' share of their
roofline: the kernels named ``trace_names.flash_window`` against the banded
causal attention those layers required (``_flash_by_kind.py``)."""
from benchmark.layer_metrics import _flash_by_kind


def read(obs):
    return _flash_by_kind.roofline(obs, "flash_window", "sliding_attention")
