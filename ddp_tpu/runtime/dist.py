"""Distributed context: process bring-up, backend selection, teardown.

Capability parity with the reference's ``utils.py:5-19``:

- ``setup(rank, world_size, backend)`` there does backend auto-selection
  (``"nccl" if torch.cuda.is_available() else "gloo"``, utils.py:6),
  env:// rendezvous (utils.py:7-11) and device pinning (utils.py:12-13).
  Here the backend switch grows the TPU branch the north-star asks for:
  ``tpu`` when TPU chips are present, else ``cpu`` (optionally with
  emulated multi-device for dev boxes — the TPU analogue of running
  2-proc gloo on a laptop).
- Multi-host rendezvous is ``jax.distributed.initialize(coordinator, N,
  id)``; the coordinator address plays MASTER_ADDR/MASTER_PORT's role.
  Unlike the reference (which never sets MASTER_ADDR — SURVEY.md §1 L2),
  single-process runs need no rendezvous at all and just work.
- ``cleanup()`` mirrors ``dist.destroy_process_group()`` (utils.py:18)
  via ``jax.distributed.shutdown()`` plus the same rank-tagged log line.

No NCCL, no CUDA: collectives lower onto ICI/DCN through XLA.
"""

from __future__ import annotations

import dataclasses
import logging
import os

logger = logging.getLogger("ddp_tpu")


def _ensure_host_device_count(n: int) -> None:
    """Request ``n`` emulated host (CPU) devices.

    Must run before the XLA CPU client is created. This is the dev-box
    stand-in for a multi-chip slice, like the reference's 2-process gloo
    quickstart (README.md:67-70) stands in for a GPU cluster.
    """
    import re

    flag = f"--xla_force_host_platform_device_count={n}"
    existing = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in existing:
        # Replace a stale count rather than silently keeping it.
        os.environ["XLA_FLAGS"] = re.sub(
            r"--xla_force_host_platform_device_count=\d+", flag, existing
        )
        return
    os.environ["XLA_FLAGS"] = (existing + " " + flag).strip()


def force_cpu_backend(num_devices: int | None = None) -> None:
    """Select the CPU platform (optionally with emulated devices).

    Call before any JAX computation.
    """
    if num_devices is not None:
        _ensure_host_device_count(num_devices)
    import jax

    jax.config.update("jax_platforms", "cpu")


# Where the persistent XLA compile cache lives when
# JAX_COMPILATION_CACHE_DIR does not place it: one fixed directory
# inside the checkout (.gitignore lists it). Fixed on purpose — a
# cache under a temporary name, a pid or a timestamp is never found
# again by the next process.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable_compile_cache() -> str | None:
    """Place the persistent compile cache; returns its directory, or
    None when it stays off.

    THE one place the cache directory is decided, called by every
    entry point that compiles for the chip (``setup`` — so the trainer
    and every launcher worker — ``scripts/serve.py``,
    ``scripts/check_kernels.py``):

    - ``JAX_COMPILATION_CACHE_DIR`` set → nothing is set in code; JAX
      reads the variable itself.
    - unset, on an accelerator → ``COMPILE_CACHE_DIR``.
    - unset, on a CPU backend → off. XLA:CPU AOT deserialization is
      machine-feature-sensitive: cache-loaded executables SIGSEGV /
      SIGABRT on resumed runs (tests/conftest.py, round 6), and a CPU
      compile is seconds, not the minute the cache exists to save.

    Reads ``jax.default_backend()``, which initializes the backend: in
    a multi-host run call it after ``jax.distributed.initialize``
    (``setup`` does).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


@dataclasses.dataclass(frozen=True)
class DistContext:
    """What the reference smears across env vars and c10d global state.

    ``process_id``/``num_processes`` are the rank/world_size analogues —
    but per *host*, not per chip: JAX owns all local chips from one
    process (SURVEY.md §2b N9).
    """

    backend: str  # resolved platform: "tpu" | "cpu" | "gpu" | plugin name
    process_id: int
    num_processes: int
    num_devices: int  # global device (chip) count
    local_device_count: int
    coordinator_address: str | None = None

    @property
    def is_main(self) -> bool:
        """True on the process that does filesystem writes and logging.

        The rank-0 role from ``train_ddp.py:204`` (checkpoint save) and
        ``train_ddp.py:201`` (loss logging).
        """
        return self.process_id == 0


_ACTIVE: DistContext | None = None


def setup(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    backend: str | None = None,
    emulate_devices: int | None = None,
) -> DistContext:
    """Bring up the distributed runtime and return its context.

    Parity with ``utils.py:5-14`` ``setup(rank, world_size, backend=None)``:
    ``backend=None`` auto-selects (tpu if present, else cpu) the way the
    reference picks nccl-if-cuda-else-gloo. Multi-host runs pass
    ``coordinator_address``/``num_processes``/``process_id`` (or rely on
    the TPU metadata auto-detection built into jax.distributed).

    ``emulate_devices=N`` forces N virtual CPU devices — the dev-box
    path used by tests and the driver's multi-chip dry run.
    """
    global _ACTIVE

    if backend == "cpu" or emulate_devices is not None:
        force_cpu_backend(emulate_devices)

    import jax

    multi_host = (
        coordinator_address is not None
        or (num_processes is not None and num_processes > 1)
        or os.environ.get("JAX_COORDINATOR_ADDRESS")
    )
    if multi_host:
        # Careful: nothing here may touch the backend (even
        # jax.default_backend() would initialize it, and initialize()
        # refuses to run after that) — decide from the requested
        # backend / platform config only.
        platforms = (
            getattr(jax.config, "jax_platforms", None)
            or os.environ.get("JAX_PLATFORMS")
            or ""
        )
        if backend == "cpu" or platforms.split(",")[0] == "cpu":
            # Multi-process collectives on the CPU backend need the
            # gloo transport; without it every cross-process program
            # dies with "Multiprocess computations aren't implemented
            # on the CPU backend" (XLA's default CPU client). This is
            # the reference's 2-proc gloo quickstart made literal —
            # and what lets the whole multihost test tier (and the
            # --spawn restart loop) run on a dev box. Must be set
            # before initialize(); harmless when already set.
            jax.config.update(
                "jax_cpu_collectives_implementation", "gloo"
            )
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )

    devices = jax.devices()
    actual = devices[0].platform
    if backend is not None and backend != actual:
        raise RuntimeError(
            f"requested backend {backend!r} but JAX resolved platform "
            f"{actual!r} — refusing to run on the wrong hardware silently"
        )
    enable_compile_cache()
    ctx = DistContext(
        backend=actual,
        process_id=jax.process_index(),
        num_processes=jax.process_count(),
        num_devices=len(devices),
        local_device_count=jax.local_device_count(),
        coordinator_address=coordinator_address,
    )
    _ACTIVE = ctx
    # Same observable bring-up line as utils.py:14's
    # "Rank {rank}/{world_size} initialized with backend {backend}".
    logger.info(
        "Process %d/%d initialized with backend %s (%d devices, %d local)",
        ctx.process_id,
        ctx.num_processes,
        ctx.backend,
        ctx.num_devices,
        ctx.local_device_count,
    )
    return ctx


def current() -> DistContext:
    """The active context, creating a single-process one if needed."""
    global _ACTIVE
    if _ACTIVE is None:
        _ACTIVE = setup()
    return _ACTIVE


def cleanup() -> None:
    """Tear down the distributed runtime (utils.py:16-19 parity)."""
    global _ACTIVE
    import jax

    ctx, _ACTIVE = _ACTIVE, None
    if ctx is None:
        return  # already torn down (or never set up) — idempotent
    if ctx.num_processes > 1:
        jax.distributed.shutdown()
    logger.info("Process %d cleanup complete", ctx.process_id)


def sync_global_devices(tag: str) -> None:
    """Host-level barrier — the ``dist.barrier()`` of train_ddp.py:63.

    Only needed for control-plane filesystem races (checkpoint discovery
    after rank-0 writes); data-plane sync is compiled into the step.
    """
    import jax

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)
