"""deepspeed_tpu — a TPU-native large-model training & inference framework.

Feature-parity rebuild of DeepSpeed (reference: carted/DeepSpeed v0.6.6,
surveyed in ``SURVEY.md``) designed TPU-first: one ``jax.sharding.Mesh``
replaces process groups, XLA collectives over ICI/DCN replace NCCL, ZeRO
stages are sharding policies, kernels are Pallas, and the train step is a
single compiled program.

Top-level API (mirrors reference ``deepspeed/__init__.py``):

- ``initialize(...)``            (:51)  → ``(engine, optimizer, dataloader, scheduler)``
- ``init_inference(...)``        (:222) → ``InferenceEngine``
- ``init_distributed(...)``      → join rendezvous + build the global mesh
- ``add_config_arguments(...)``  (:206) → argparse plumbing
"""
from __future__ import annotations

__version__ = "0.1.0"

from . import comm  # noqa: F401
from . import telemetry  # noqa: F401  (metrics registry / tracer / watchdog)
from .parallel import zero  # noqa: F401  (deepspeed.zero.Init parity namespace)
from .comm import init_distributed  # noqa: F401
from .runtime.config import Config, DeepSpeedConfig  # noqa: F401


def initialize(args=None, model=None, optimizer=None, model_parameters=None,
               training_data=None, lr_scheduler=None, mesh=None, config=None,
               config_params=None, loss_fn=None, rngs=None, collate_fn=None,
               dist_init_required=None):
    """Build a training :class:`~deepspeed_tpu.runtime.engine.Engine`.

    Mirrors ``deepspeed.initialize`` (reference ``deepspeed/__init__.py:51``)
    and returns the same 4-tuple ``(engine, optimizer, dataloader,
    lr_scheduler)``.  ``model`` is a flax module (or anything with
    ``init``/``apply``); ``loss_fn(model_out, batch) -> scalar`` is optional
    when the model itself returns a loss.
    """
    from .runtime.engine import Engine

    if config is None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    with telemetry.trace.span("init/engine"):
        engine = Engine(
            model=model,
            config=config,
            optimizer=optimizer,
            model_parameters=model_parameters,
            training_data=training_data,
            lr_scheduler=lr_scheduler,
            mesh=mesh,
            loss_fn=loss_fn,
            rngs=rngs,
            collate_fn=collate_fn,
            dist_init_required=dist_init_required,
        )
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, **kwargs):
    """Build an :class:`~deepspeed_tpu.inference.engine.InferenceEngine`.

    Mirrors ``deepspeed.init_inference`` (reference ``deepspeed/__init__.py:222``).
    """
    from .inference.engine import InferenceEngine

    return InferenceEngine(model=model, config=config, **kwargs)


def add_config_arguments(parser):
    """Add ``--deepspeed``/``--deepspeed_config`` CLI args (reference :206)."""
    group = parser.add_argument_group("DeepSpeed-TPU", "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (always on; kept for parity)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the JSON config file")
    group.add_argument("--local_rank", type=int, default=-1,
                       help="Accepted for launcher parity; unused (one process per host)")
    return parser
