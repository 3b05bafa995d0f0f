"""The chip: found or refused, described, and its memory read."""

from __future__ import annotations

import os
import sys


class NoChip(SystemExit):
    """Raised (exit code 3) where the measuring path finds no TPU or
    fewer chips than the cell asks for. No result line is printed: a
    number from a CPU is never written under a device metric's name."""


def require_tpu(chips: int):
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: no accelerator: {e}", file=sys.stderr)
        raise NoChip(3) from e
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(
            f"benchmark: needs {chips} TPU chip(s), found "
            f"{len(devs)} x {devs[0].platform}; refusing to measure",
            file=sys.stderr,
        )
        raise NoChip(3)
    return devs


def place_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed path inside the checkout
    (``.jax_cache/``), unless ``JAX_COMPILATION_CACHE_DIR`` places it.
    Every program is cached, however quick its compile: a run after the
    first compiles nothing."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def describe(devs, chips: int) -> dict:
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": memory_peak_bytes(devs[:chips]),
    }


def memory_peak_bytes(devs) -> int:
    """Peak on the fullest chip, as the backend reports it."""
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
