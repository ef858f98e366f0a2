"""Optimizer builder: config name → optax transformation.

Covers the reference's optimizer dispatch (``engine.py:1117``
``_configure_basic_optimizer``): Adam/AdamW (torch or ``FusedAdam``
``csrc/adam/multi_tensor_adam.cu`` — on TPU one XLA-fused update program IS
the fused path), ``FusedLamb`` (``csrc/lamb/fused_lamb_cuda_kernel.cu``),
SGD, Adagrad, plus Lion.  The 1-bit family (OnebitAdam/OnebitLamb/
ZeroOneAdam, ``runtime/fp16/onebit/``) lives in ``ops/onebit.py`` and is
wired here by name.
"""
from __future__ import annotations

from typing import Callable, Optional, Union

import optax

from ..telemetry import trace
from . import constants as C
from .config import Config, OptimizerConfig

ScalarOrSchedule = Union[float, Callable]


def decay_mask(model) -> Optional[Callable]:
    """``params -> tree of bools`` (True: the leaf decays) for a model whose
    ``is_undecayed_leaf`` is a predicate over a leaf's path of dict keys;
    None where every leaf decays (no such attribute, or None), and the
    chain is then built as it always was."""
    skip = getattr(model, "is_undecayed_leaf", None)
    if skip is None:
        return None
    import jax

    return lambda params: jax.tree_util.tree_map_with_path(
        lambda path, _: not skip(tuple(getattr(k, "key", k) for k in path)),
        params)


def build_optimizer(cfg: OptimizerConfig,
                    learning_rate: Optional[ScalarOrSchedule] = None,
                    mask: Optional[Callable] = None
                    ) -> optax.GradientTransformation:
    """``mask`` (:func:`decay_mask`): which leaves weight decay touches."""
    lr = learning_rate if learning_rate is not None else cfg.lr
    b1, b2 = cfg.betas
    name = cfg.type
    if name in (C.ADAM_OPTIMIZER, C.ADAMW_OPTIMIZER):
        adam_w_mode = cfg.extra.get("adam_w_mode", name == C.ADAMW_OPTIMIZER)
        if adam_w_mode or cfg.weight_decay == 0.0:
            return optax.adamw(lr, b1=b1, b2=b2, eps=cfg.eps,
                               weight_decay=cfg.weight_decay, mask=mask)
        # plain Adam + L2 (decay inside the gradient), reference cpu_adam's
        # non-decoupled mode
        return optax.chain(optax.add_decayed_weights(cfg.weight_decay, mask),
                           optax.adam(lr, b1=b1, b2=b2, eps=cfg.eps))
    if name in (C.ADAM8BIT_OPTIMIZER, C.ADAMW8BIT_OPTIMIZER):
        # int8 Adam moments (ops/adam8bit.py): the single-chip analog of
        # sharding optimizer state across a ZeRO data-parallel group
        from ..ops.adam8bit import adamw_8bit
        wd = cfg.weight_decay if name == C.ADAMW8BIT_OPTIMIZER or \
            cfg.extra.get("adam_w_mode", False) else 0.0
        tx = adamw_8bit(lr, b1=b1, b2=b2, eps=cfg.eps, weight_decay=wd,
                        mask=mask)
        if name == C.ADAM8BIT_OPTIMIZER and cfg.weight_decay and not wd:
            tx = optax.chain(optax.add_decayed_weights(cfg.weight_decay, mask),
                             tx)
        return tx
    if name == C.LAMB_OPTIMIZER:
        return optax.lamb(lr, b1=b1, b2=b2, eps=cfg.eps,
                          weight_decay=cfg.weight_decay, mask=mask)
    if name == C.SGD_OPTIMIZER:
        return optax.sgd(lr, momentum=cfg.extra.get("momentum", 0.0),
                         nesterov=bool(cfg.extra.get("nesterov", False)))
    if name == C.ADAGRAD_OPTIMIZER:
        return optax.adagrad(lr, eps=cfg.eps)
    if name == C.LION_OPTIMIZER:
        return optax.lion(lr, b1=b1, b2=b2, weight_decay=cfg.weight_decay,
                          mask=mask)
    if name in (C.ONEBIT_ADAM_OPTIMIZER, C.ONEBIT_LAMB_OPTIMIZER,
                C.ZERO_ONE_ADAM_OPTIMIZER):
        try:
            from ..ops.onebit import build_onebit_optimizer
        except ImportError as e:
            raise NotImplementedError(
                f"optimizer {name!r} (compressed-communication family) is not "
                "built yet in this installation") from e
        return build_onebit_optimizer(name, cfg, lr)
    raise ValueError(f"unknown optimizer {name!r}; valid: {C.DEEPSPEED_OPTIMIZERS}")


def clip_by_global_norm(max_norm: float) -> optax.GradientTransformation:
    """``optax.clip_by_global_norm`` whose operations carry the device
    scope ``grad_clip`` (metadata only)."""
    clip = optax.clip_by_global_norm(max_norm)

    def update(updates, state, params=None):
        with trace.device_span("grad_clip"):
            return clip.update(updates, state, params)

    return optax.GradientTransformation(clip.init, update)


def build_tx(config: Config, learning_rate: Optional[ScalarOrSchedule] = None,
             mask: Optional[Callable] = None
             ) -> optax.GradientTransformation:
    """Full gradient-transformation chain: clip → optimizer.

    Clipping uses the global norm across the whole (sharded) grad tree,
    matching reference ``runtime/utils.py`` ``clip_grad_norm_`` semantics —
    under pjit the norm reduction is a cross-shard psum inserted by XLA.
    """
    parts = []
    if config.gradient_clipping and config.gradient_clipping > 0:
        parts.append(clip_by_global_norm(config.gradient_clipping))
    parts.append(build_optimizer(config.optimizer, learning_rate, mask))
    return optax.chain(*parts) if len(parts) > 1 else parts[0]
