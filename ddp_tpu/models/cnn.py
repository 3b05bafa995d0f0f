"""SimpleCNN — the reference's flagship model, re-expressed for TPU.

Capability parity with ``model.py:4-20`` (``SimpleCNN(nn.Module)``):
Conv2d(1→32, 3×3, pad 1) → ReLU → Conv2d(32→64, 3×3, pad 1) → ReLU →
Flatten → Linear(64·28·28 → 10), 520,586 parameters. Differences are
deliberate TPU idiom, not behavior:

- NHWC layout (TPU-native; the reference is NCHW) — flatten order
  therefore differs, but the function class and parameter count are
  identical.
- Weights are initialized from an explicit PRNG key; running the same
  seed on every process replaces DDP's constructor-time rank-0
  parameter broadcast (train_ddp.py:34) with determinism by
  construction.
"""

from __future__ import annotations

import jax.numpy as jnp

from ddp_tpu.obs.tracer import importing

with importing("flax"):
    import flax.linen as nn


class SimpleCNN(nn.Module):
    """2-conv + linear MNIST classifier (model.py:4-20 parity).

    ``features`` defaults to the reference's (32, 64); tests shrink it
    to keep emulated-CPU runs cheap.
    """

    num_classes: int = 10
    features: tuple[int, int] = (32, 64)

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        # x: [B, 28, 28, 1] float. SAME padding preserves 28×28 like the
        # reference's padding=1 (model.py:9,12).
        x = nn.Conv(
            features=self.features[0], kernel_size=(3, 3), padding="SAME", name="conv1"
        )(x)
        x = nn.relu(x)
        x = nn.Conv(
            features=self.features[1], kernel_size=(3, 3), padding="SAME", name="conv2"
        )(x)
        x = nn.relu(x)
        x = x.reshape((x.shape[0], -1))  # Flatten (model.py:15)
        x = nn.Dense(features=self.num_classes, name="fc")(x)  # model.py:16
        return x
