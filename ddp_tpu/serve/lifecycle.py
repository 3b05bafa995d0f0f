"""Zero-downtime model lifecycle: streaming restore + verified hot-swap.

Production fleets ship new checkpoints without restarting; this module
is the host-side half of that contract (the device-side swap lives in
serve/engine.install_params, the HTTP surface in serve/server.py
``POST /reload`` and serve/fleet.py ``POST /reloadz``):

  verify → load → drain-to-barrier → swap → (rollback on any failure)

``verify_reload_target`` runs BEFORE anything touches device state:
the incoming checkpoint must carry a readable PR-5 integrity manifest
(``manifest_missing``), every listed file must match its recorded
size + CRC (``crc_mismatch``), and the spec derived from its metadata
+ ``lm_spec.json`` sidecar must equal the serving spec exactly
(``spec_skew`` — the compiled program set is shape-addressed, so a
skewed tree could never install atomically). The three rejection
reasons are named constants because the fleet's reload loop and the
chaos drills pin them.

``StreamingRestore`` is the startup-latency half: a cold replica pays
restore THEN warmup serially; a streaming replica runs the orbax
partial restores on a background thread — embedding + first-K blocks
land first and open admission (requests queue against the paused
engine), the deep blocks land behind them — while the main thread
compiles the program set over same-shaped init params. Dispatch
correctness still requires the full tree (the forward pass reads
every layer), so first dispatch waits for full residency; the win is
wall-clock overlap (restore I/O behind XLA compiles, admission open
early); drilled by tests/test_lifecycle.py, not measured on a chip.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import re
import threading
import time
from typing import Any, Iterable, Optional

logger = logging.getLogger("ddp_tpu")

# The named rejection reasons a reload can answer with — verification
# happens before device state is touched, so every one of these leaves
# the old model serving untouched.
REASON_MANIFEST_MISSING = "manifest_missing"
REASON_CRC_MISMATCH = "crc_mismatch"
REASON_SPEC_SKEW = "spec_skew"
REJECTION_REASONS = (
    REASON_MANIFEST_MISSING,
    REASON_CRC_MISMATCH,
    REASON_SPEC_SKEW,
)


class ReloadRejected(Exception):
    """A reload target verification failed — nothing was installed.

    ``reason`` is one of ``REJECTION_REASONS`` (the HTTP payload's
    ``error`` field); ``detail`` is the human-readable evidence.
    """

    def __init__(self, reason: str, detail: str):
        super().__init__(f"{reason}: {detail}")
        self.reason = reason
        self.detail = detail


def model_version_token(directory: str, epoch: int) -> str:
    """Canonical version label for "checkpoint dir D at epoch N".

    What /healthz advertises, serve_request records carry, and the
    fleet's convergence check compares — stable across replicas and
    restarts because it names the artifact, not the process.
    """
    base = os.path.basename(os.path.normpath(os.path.abspath(directory)))
    return f"{base}@epoch{epoch}"


@dataclasses.dataclass(frozen=True)
class ReloadTarget:
    """A verified swap target: everything the load + swap stages need,
    produced only by ``verify_reload_target``."""

    directory: str
    epoch: int
    version: str
    spec: Any


def verify_reload_target(
    directory: str,
    *,
    epoch: Optional[int] = None,
    current_spec: Any = None,
    num_heads_fallback: int = 4,
) -> ReloadTarget:
    """Verify a checkpoint as a hot-swap target → ``ReloadTarget``.

    Raises ``ReloadRejected`` with a named reason on any failure; no
    tensor data is read (metadata + manifest CRCs only), and device
    state is never touched — the caller's old model keeps serving.

    Deliberately STRICTER than the restore path: ``restore`` accepts
    manifest-less checkpoints for compatibility, but a hot-swap's
    failure mode is a live fleet serving a half-trusted model — no
    manifest, no swap.
    """
    from ddp_tpu.train.checkpoint import (
        CheckpointManager,
        derive_spec_with_sidecar,
        verify_manifest,
    )

    mgr = CheckpointManager(directory)
    try:
        if epoch is None:
            epoch = mgr.latest_epoch()
            if epoch is None:
                raise ReloadRejected(
                    REASON_MANIFEST_MISSING,
                    f"no checkpoint found in {directory}",
                )
        epoch = int(epoch)
        problems = verify_manifest(mgr.directory, epoch)
        if problems is None:
            raise ReloadRejected(
                REASON_MANIFEST_MISSING,
                f"epoch {epoch} has no readable integrity manifest — "
                "refusing to hot-swap an unverifiable checkpoint",
            )
        if problems:
            raise ReloadRejected(REASON_CRC_MISMATCH, "; ".join(problems))
        try:
            meta = mgr.params_metadata(epoch)
            spec = derive_spec_with_sidecar(
                directory, meta, num_heads_fallback=num_heads_fallback
            )
        except (KeyError, ValueError) as e:
            raise ReloadRejected(REASON_SPEC_SKEW, str(e))
        if current_spec is not None and spec != current_spec:
            diffs = [
                f"{f}: {getattr(spec, f)!r} != serving "
                f"{getattr(current_spec, f)!r}"
                for f in type(current_spec)._fields
                if getattr(spec, f, None) != getattr(current_spec, f)
            ]
            raise ReloadRejected(
                REASON_SPEC_SKEW,
                "; ".join(diffs) or "spec differs from the serving spec",
            )
        return ReloadTarget(
            directory=os.path.abspath(directory),
            epoch=epoch,
            version=model_version_token(directory, epoch),
            spec=spec,
        )
    finally:
        mgr.close()


def load_reload_target(target: ReloadTarget) -> Any:
    """Host-side restore of a verified target's params.

    The old model keeps serving throughout — nothing here touches the
    engine; the returned tree goes to ``engine.install_params`` once
    the lanes have drained to the swap barrier. Integrity-verified
    discovery and the qkv-format gate ride along for free
    (``restore_for_inference``).
    """
    from ddp_tpu.train.checkpoint import CheckpointManager

    mgr = CheckpointManager(target.directory)
    try:
        params, _, _ = mgr.restore_for_inference(target.epoch)
        return params
    finally:
        mgr.close()


# ---------------------------------------------------------------------
# Streaming restore
# ---------------------------------------------------------------------

_BLOCK_RE = re.compile(r"^block(\d+)$")


def split_param_groups(
    children: Iterable[str], first_blocks: int = 1
) -> tuple[list[str], list[str]]:
    """Param children → (admission group, deep group), in model order.

    The embedding, the positional table and the first
    ``first_blocks`` transformer blocks gate admission (they are what
    a prefill touches first); every deeper block and the final norm
    stream behind them. Unknown children land in the deep group — a
    foreign tree just degrades to "everything gates on full
    residency", never to serving without a layer.
    """
    names = [str(c) for c in children]
    blocks = sorted(
        (int(m.group(1)), n)
        for n in names
        for m in [_BLOCK_RE.match(n)]
        if m
    )
    early = {n for _, n in blocks[: max(0, first_blocks)]}
    # Emit MODEL order (embed → pos_embed → blocks ascending → rest),
    # not input order: metadata trees iterate alphabetically, and the
    # whole point of the admission group is restoring what a prefill
    # touches first, first.
    ordered = [n for n in ("embed", "pos_embed") if n in names]
    ordered += [n for _, n in blocks]
    ordered += sorted(
        n for n in names
        if n not in ordered
    )
    admission, deep = [], []
    for n in ordered:
        if n in ("embed", "pos_embed") or n in early:
            admission.append(n)
        else:
            deep.append(n)
    return admission, deep


class StreamingRestore:
    """Layer-streamed checkpoint restore for serving startup.

    Construction resolves the epoch (verified discovery) and derives
    the spec from checkpoint METADATA + the ``lm_spec.json`` sidecar —
    no tensor data read — so the caller can build and warm up the
    engine over init params while ``start()``'s background thread
    restores the real weights in residency order: the admission group
    first (``wait_admission`` returns → open the front door, requests
    queue), then the deep group (``wait`` returns the full tree →
    ``engine.install_params`` + resume admission). The deep phase
    re-checks the qkv-format sidecar exactly like
    ``restore_for_inference`` does.
    """

    def __init__(
        self,
        directory: str,
        *,
        epoch: Optional[int] = None,
        first_blocks: int = 1,
        num_heads_fallback: int = 4,
    ):
        from ddp_tpu.train.checkpoint import (
            CheckpointManager,
            derive_spec_with_sidecar,
        )

        self.directory = directory
        self._mgr = CheckpointManager(directory)
        try:
            if epoch is None:
                epoch = self._mgr.latest_intact_epoch()
                if epoch is None:
                    raise FileNotFoundError(
                        f"no checkpoints in {directory}"
                    )
            self.epoch = int(epoch)
            meta = self._mgr.params_metadata(self.epoch)
        except Exception:
            self._mgr.close()
            raise
        self.version = model_version_token(directory, self.epoch)
        self.spec = derive_spec_with_sidecar(
            directory, meta, num_heads_fallback=num_heads_fallback
        )
        self._children = [str(k) for k in meta]
        self._meta = meta
        self.admission_group, self.deep_group = split_param_groups(
            self._children, first_blocks
        )
        self._params: dict = {}
        self._error: Optional[str] = None
        self._admission_evt = threading.Event()
        self._done_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._t0: Optional[float] = None
        # Wall seconds from start() to each residency milestone — what
        # the bench's cold-vs-streaming TTFT split reads.
        self.admission_ready_s: Optional[float] = None
        self.complete_s: Optional[float] = None

    def placeholder_params(self) -> dict:
        """A zeros tree with the checkpoint's exact shapes/dtypes —
        the stand-in the engine builds and warms up over while the
        real weights stream. Zeros, not a random init: allocation is
        near-free, while seeding a real init costs seconds of PRNG
        work at exactly the moment the overlap is supposed to be
        winning (warmup compiles only care about shapes)."""
        import jax
        import jax.numpy as jnp

        return jax.tree.map(
            lambda m: jnp.zeros(m.shape, m.dtype), self._meta
        )

    def start(self) -> "StreamingRestore":
        if self._thread is not None:
            raise RuntimeError("streaming restore already started")
        self._t0 = time.monotonic()
        self._thread = threading.Thread(
            target=self._run, name="stream-restore", daemon=True
        )
        self._thread.start()
        return self

    def _run(self) -> None:
        try:
            got = self._mgr.read_params_children(
                self.epoch, self.admission_group
            )
            self._params.update(got)
            self.admission_ready_s = round(
                time.monotonic() - self._t0, 4
            )
            self._admission_evt.set()
            got = self._mgr.read_params_children(
                self.epoch, self.deep_group
            )
            self._params.update(got)
            missing = [
                c for c in self._children if c not in self._params
            ]
            if missing:
                raise RuntimeError(
                    f"streamed restore left {missing} unrestored"
                )
            from ddp_tpu.train.checkpoint import _check_qkv_format

            fmt = self._mgr.read_partial(self.epoch, ("fmt",)).get("fmt")
            _check_qkv_format(
                int(fmt) if fmt is not None else None,
                self._params,
                f"checkpoint epoch {self.epoch}",
            )
            self.complete_s = round(time.monotonic() - self._t0, 4)
        except Exception as e:  # noqa: BLE001 — surfaced to waiters
            self._error = f"{type(e).__name__}: {e}"
        finally:
            # Waiters always wake; they check _error first.
            self._admission_evt.set()
            self._done_evt.set()
            try:
                self._mgr.close()
            except Exception:  # noqa: BLE001 — best-effort cleanup
                pass

    def wait_admission(self, timeout: Optional[float] = None) -> bool:
        """True once the admission group is resident (open the front
        door); raises if the restore already failed."""
        ok = self._admission_evt.wait(timeout)
        if self._error is not None:
            raise RuntimeError(
                f"streaming restore failed: {self._error}"
            )
        return ok

    def wait(self, timeout: Optional[float] = None) -> dict:
        """Block to FULL residency → the complete params tree."""
        if not self._done_evt.wait(timeout):
            raise TimeoutError(
                f"streaming restore of {self.directory} epoch "
                f"{self.epoch} did not finish in {timeout}s"
            )
        if self._error is not None:
            raise RuntimeError(
                f"streaming restore failed: {self._error}"
            )
        return self._params
