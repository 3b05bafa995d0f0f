"""Training rows: uniform random token ids from the seed, every row
different. Parameters (the traffic file): ``steps_per_epoch`` (rows are
``steps_per_epoch x global batch``), and for the driver ``block_steps``,
``warm_steps``."""

from __future__ import annotations

import numpy as np


def generate(params: dict, *, seed: int, vocab_size: int, seq_len: int,
             global_batch: int) -> np.ndarray:
    rows = int(params["steps_per_epoch"]) * global_batch
    rng = np.random.default_rng([int(seed), 0x70C5])
    return rng.integers(
        0, vocab_size, size=(rows, seq_len), dtype=np.int32
    )
