"""Input pipeline: per-process sharded batching with device prefetch.

Capability parity with ``data.py:21-25`` (``DataLoader(num_workers=2,
pin_memory=True)`` over a ``DistributedSampler``), redesigned for the
TPU execution model:

- The reference overlaps host decode with compute via worker
  subprocesses and pins host memory for async H2D copies. Here the
  equivalent is double-buffered ``jax.device_put``: batch ``i+1`` is
  dispatched to the devices while batch ``i``'s step runs — JAX
  transfers are async, so one Python thread suffices where torch needs
  a worker pool.
- Each *process* materializes only its shard (``ShardSampler`` with
  ``num_shards = process_count``); the global array is assembled from
  process-local shards with ``make_array_from_process_local_data``, so
  no host ever holds the global batch — this is what makes the same
  loader multi-host-correct where the reference's per-rank DataLoader
  pattern is.
- uint8 images travel to the device; the float conversion (ToTensor's
  /255) happens inside the jitted step on the MXU-adjacent VPU, saving
  4× host→device bandwidth.
"""

from __future__ import annotations

from typing import Iterator, NamedTuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddp_tpu.data.sampler import ShardSampler
from ddp_tpu.obs.tracer import Tracer, get_tracer
from ddp_tpu.runtime.mesh import data_axes


class Batch(NamedTuple):
    images: jax.Array  # [B, H, W, C] uint8, sharded over the data axes
    labels: jax.Array  # [B] int32, sharded over the data axes


class ShardedLoader:
    """Deterministic, epoch-reshuffled, device-sharded batch stream."""

    # Below this many bytes per local batch the native worker pool is
    # auto-disabled — the handoff overhead exceeds the gather it
    # offloads (MNIST-sized rows lose, ImageNet-sized rows win).
    POOL_MIN_BATCH_BYTES = 1 << 20

    @classmethod
    def pool_would_engage(cls, batch_bytes: int) -> bool:
        """The native-pool gate: big-enough batches AND a spare core.

        Single source of the policy — the loader consults it at
        construction.
        """
        import os

        return (
            batch_bytes >= cls.POOL_MIN_BATCH_BYTES
            and (os.cpu_count() or 1) >= 2
        )

    def __init__(
        self,
        images: np.ndarray,
        labels: np.ndarray,
        mesh: Mesh,
        global_batch_size: int,
        *,
        shuffle: bool = True,
        seed: int = 0,
        num_workers: int = 0,
        tracer: Tracer | None = None,
    ):
        self.mesh = mesh
        # ``data.next_batch`` spans (obs/tracer.py): the process-global
        # tracer unless handed another.
        self.tracer = tracer if tracer is not None else get_tracer()
        self.global_batch_size = global_batch_size
        procs = jax.process_count()
        if global_batch_size % procs:
            raise ValueError(
                f"global batch {global_batch_size} not divisible by {procs} processes"
            )
        shard_count = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
        if global_batch_size % shard_count:
            raise ValueError(
                f"global batch {global_batch_size} not divisible by "
                f"{shard_count} data-parallel shards"
            )
        self.local_batch_size = global_batch_size // procs
        spec = P(data_axes(self.mesh))
        self._img_sharding = NamedSharding(mesh, spec)
        self._lbl_sharding = NamedSharding(mesh, spec)
        if procs > 1:
            # Each process materializes a DISJOINT contiguous sample
            # shard. That is only well-defined when every device's
            # batch slice lies inside its own process's block —
            # otherwise the assembled array would hold
            # replicated-but-different blocks (e.g. a non-data axis
            # like pipe spanning the processes while batch blocks
            # replicate across it).
            shape = (global_batch_size, *images.shape[1:])
            for dev, idx in self._img_sharding.devices_indices_map(
                shape
            ).items():
                sl = idx[0]
                lo = 0 if sl.start is None else sl.start
                hi = global_batch_size if sl.stop is None else sl.stop
                p = dev.process_index
                if lo < p * self.local_batch_size or hi > (p + 1) * self.local_batch_size:
                    raise ValueError(
                        f"device {dev} (process {p}) covers batch rows "
                        f"[{lo}, {hi}) outside its process's block — "
                        f"this mesh cannot be fed by process-sharded "
                        f"loading; give the mesh a data axis spanning "
                        f"the processes"
                    )
        self.images = images
        self.labels = labels
        # Shard the *sample stream* by process; device-level sharding of
        # each assembled batch is handled by the sharding spec above.
        self.sampler = ShardSampler(
            num_examples=len(images),
            num_shards=procs,
            shard_id=jax.process_index(),
            shuffle=shuffle,
            seed=seed,
        )
        # Optional native worker pool — the C++ analogue of the
        # reference's DataLoader(num_workers=2) (data.py:22). 0 keeps
        # the single-thread Python gather; >0 tries the native path and
        # falls back (with a warning) if no toolchain is available.
        self._prefetcher = None
        if num_workers > 0 and images.dtype != np.uint8:
            # The C++ gather ring is a byte-pipeline (uint8 images);
            # float feature streams (e.g. the long-context sequences)
            # use the Python gather, which is not the bottleneck there.
            import logging

            logging.getLogger("ddp_tpu").warning(
                "num_workers=%d requested but the native pipeline is "
                "uint8-only (%s data); using Python gather",
                num_workers,
                images.dtype,
            )
            num_workers = 0
        if num_workers > 0:
            import os as _os

            batch_bytes = self.local_batch_size * int(
                np.prod(images.shape[1:])
            )
            if not self.pool_would_engage(batch_bytes):
                # A worker pool is overhead, not help, when one batch
                # gathers in microseconds (MNIST-sized rows) or when
                # there is no spare core to run it on — the ticket/
                # slot handoff costs more than the memcpy it offloads.
                # Auto-disable instead of making the reference's
                # num_workers=2 default a pessimization.
                import logging

                logging.getLogger("ddp_tpu").info(
                    "num_workers=%d auto-disabled: %d-byte batches, "
                    "%s host cores (pool threshold: %d bytes and >1 "
                    "core)",
                    num_workers, batch_bytes, _os.cpu_count(),
                    self.POOL_MIN_BATCH_BYTES,
                )
                num_workers = 0
        if num_workers > 0:
            from ddp_tpu import native

            if native.available():
                self._prefetcher = native.NativePrefetcher(
                    self.images,
                    self.labels,
                    self.local_batch_size,
                    num_workers=num_workers,
                )
            else:
                import logging

                logging.getLogger("ddp_tpu").warning(
                    "num_workers=%d requested but native pipeline "
                    "unavailable; using Python gather",
                    num_workers,
                )

    def steps_per_epoch(self) -> int:
        # The final partial batch is always dropped: SPMD steps need
        # static shapes, and re-padding mid-epoch isn't worth a
        # recompile for <1 batch of data (the reference's DataLoader
        # keeps it, at 60000/64 a 0.05% difference per epoch).
        return self.sampler.shard_size // self.local_batch_size

    def _host_batches(
        self, epoch: int, skip_batches: int = 0
    ) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        idx = self.sampler.shard_indices(epoch)
        if skip_batches:
            # Mid-epoch resume: the index plan is deterministic in
            # (seed, epoch), so dropping the consumed prefix continues
            # the exact same data order.
            idx = idx[skip_batches * self.local_batch_size :]
        if self._prefetcher is not None:
            yield from self._prefetcher.epoch(idx)
            return
        lb = self.local_batch_size
        n_full = len(idx) // lb
        for b in range(n_full):
            sel = idx[b * lb : (b + 1) * lb]
            yield self.images[sel], self.labels[sel]

    def close(self) -> None:
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None

    def epoch(self, epoch: int, skip_batches: int = 0) -> Iterator[Batch]:
        """Batches for ``epoch``, prefetched one step ahead.

        ``epoch`` plays the role of ``sampler.set_epoch(epoch)`` at
        train_ddp.py:193 — same data order on re-runs, reshuffled per
        epoch. ``skip_batches`` resumes mid-epoch after a preemption
        save (the consumed prefix of the deterministic plan is
        dropped).
        """

        def put(img_np: np.ndarray, lbl_np: np.ndarray) -> Batch:
            if jax.process_count() == 1:
                return Batch(
                    jax.device_put(img_np, self._img_sharding),
                    jax.device_put(lbl_np, self._lbl_sharding),
                )
            return Batch(
                jax.make_array_from_process_local_data(self._img_sharding, img_np),
                jax.make_array_from_process_local_data(self._lbl_sharding, lbl_np),
            )

        # ``data.next_batch``: the host's share of one fetch — gather
        # the next batch's rows and dispatch its transfer. With the
        # one-step prefetch this is what the consumer's ``next()``
        # waits for. The fetch that finds the epoch exhausted records
        # rows 0.
        tracer, rows = self.tracer, self.local_batch_size
        host = self._host_batches(epoch, skip_batches)
        pending: Batch | None = None
        while True:
            with tracer.span("data.next_batch", nums=(rows,)) as span:
                item = next(host, None)
                if item is None:
                    span.nums = (0,)
                else:
                    # async dispatch — overlaps the prior step
                    nxt = put(*item)
            if item is None:
                break
            if pending is not None:
                yield pending
            pending = nxt
        if pending is not None:
            yield pending
