"""ddp_tpu — a TPU-native distributed data-parallel training framework.

A ground-up JAX/XLA re-design of the capability surface of
``zahmedy/PyTorch-Distributed-Data-Parallel-DDP-Trainer`` (the reference):
multi-process SPMD launch, process-group init/teardown with backend
selection, data-parallel training with gradient all-reduce, per-rank
deterministic data sharding with per-epoch shuffling, rank-0
checkpointing, and latest-checkpoint auto-resume — expressed as
``jax.distributed`` + ``Mesh`` + ``shard_map``/``pjit`` + ``lax.pmean``
+ Orbax, not as a port of the reference's torch/c10d architecture.

Layer map (mirrors SURVEY.md §1, re-homed for TPU):

  L5  CLI / launcher       train.py (repo root)
  L4  Orchestration        ddp_tpu.train.trainer
  L3  Models / Data        ddp_tpu.models / ddp_tpu.data
  L2  Runtime              ddp_tpu.runtime (dist context, mesh)
  L1  Native               XLA:TPU compiler, ICI collectives, Pallas
                           kernels (ddp_tpu.ops), C++ data plane
"""

__version__ = "0.2.0"

from ddp_tpu.runtime.dist import DistContext, setup, cleanup  # noqa: F401
from ddp_tpu.runtime.mesh import MeshSpec, make_mesh  # noqa: F401


def __getattr__(name):
    """Lazy top-level API: ``from ddp_tpu import Trainer, TrainConfig``.

    Deferred imports keep ``import ddp_tpu`` light (no flax/optax/orbax
    pull-in) for tools that only need the runtime layer.
    """
    if name == "Trainer":
        from ddp_tpu.train.trainer import Trainer

        return Trainer
    if name == "TrainConfig":
        from ddp_tpu.train.config import TrainConfig

        return TrainConfig
    if name == "CheckpointManager":
        from ddp_tpu.train.checkpoint import CheckpointManager

        return CheckpointManager
    if name == "get_model":
        from ddp_tpu.models import get_model

        return get_model
    raise AttributeError(f"module 'ddp_tpu' has no attribute {name!r}")
