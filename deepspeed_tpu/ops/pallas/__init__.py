"""Pallas TPU kernels — the ``csrc/`` (CUDA kernel) analog.

Kernel inventory mapping to reference native components (SURVEY.md §2.4):
``flash_attention`` ↔ fused training/inference attention,
``decode_attention`` ↔ KV-cache softmax-context inference kernel,
``fused_mlp`` ↔ the feed-forward pair around ``gelu_kernels.cu``;
block-sparse attention lives in ``ops/sparse_attention``; grouped
quantization in ``ops/quantizer``.
"""
from .decode_attention import decode_attention  # noqa: F401
from .paged_attention import paged_decode_attention  # noqa: F401
from .flash_attention import flash_attention  # noqa: F401
