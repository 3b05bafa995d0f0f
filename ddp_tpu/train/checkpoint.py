"""Checkpoint save / discovery / resume — the reference's biggest subsystem.

Contract parity with train_ddp.py (≈136 of its 227 trainer lines,
SURVEY.md §5):

- save once per epoch into ``./checkpoints`` with the epoch number in
  the path (train_ddp.py:204-209);
- on startup, discover the latest checkpoint and resume from
  ``epoch + 1`` (train_ddp.py:49-89), from-scratch when none exists;
- restore must leave *every* process with identical state — the
  reference hand-rolls a 130-line byte-level broadcast protocol for
  this (train_ddp.py:100-186); Orbax restore is collective by design,
  so the protocol collapses into one call.

Deliberate divergences from the reference's literal behavior (its
*intent* per README.md:47, with its verified defects fixed —
SURVEY.md §2a #8):

- optimizer state IS restored (the reference reads ``ckpt["optimizer"]``
  at train_ddp.py:88 and silently drops it);
- "latest" means highest epoch number, not newest st_ctime
  (train_ddp.py:57) — ctime ordering breaks under copy/restore of the
  checkpoint dir;
- saves are atomic (Orbax commit-dir protocol), so a crash mid-save
  can't leave a corrupt "latest" for discovery to trip on;
- the broadcast-resume protocol's four bugs (missing src, stale local
  num_keys, undefined model_state off rank 0, dropped optimizer state)
  have no analogue here by construction.
"""

from __future__ import annotations

import time

_IMPORT_T0 = time.perf_counter()  # → ``startup.import``, at the last line

import json
import logging
import os
import zlib
from typing import Any, Sequence

import jax
import numpy as np

from ddp_tpu.obs.tracer import imported, importing

with importing("orbax.checkpoint"):
    import orbax.checkpoint as ocp

from ddp_tpu.parallel.ddp import TrainState

logger = logging.getLogger("ddp_tpu")

# Checkpoint format version, saved as a ``fmt`` scalar alongside the
# state. The qkv-layout ladder (models/vit.py MultiHeadAttention):
#   1 — (no ``fmt`` key) q/k/v-major fused columns;
#   2 — HEAD-MAJOR MHA columns ([head, q|k|v, head_dim], round 3: TP
#       shards are whole heads) and BLOCK-layout GQA columns
#       ([q·H | k·H_kv | v·H_kv]);
#   3 — GROUP-MAJOR GQA columns ([kv-group: q·G | k | v] × H_kv,
#       round 4: GQA×TP shards are whole kv groups). MHA trees are
#       bit-identical between 2 and 3 and restore freely.
# Each step has IDENTICAL shapes to the last, so a silent restore
# would scramble attention — restore refuses stale attention-bearing
# trees and points at scripts/convert_qkv_layout.py instead.
CHECKPOINT_FORMAT = 3


def _has_fused_qkv(tree: Any) -> bool:
    """Does any leaf path contain an ``attn/qkv`` projection?"""
    found = False

    def visit(path, _):
        nonlocal found
        keys = [str(getattr(k, "key", k)) for k in path]
        if "qkv" in keys:
            found = True

    jax.tree_util.tree_map_with_path(visit, tree)
    return found


def _has_gqa_qkv(tree: Any) -> bool:
    """Any ``attn/qkv`` KERNEL with out-dim ≠ 3×in-dim (the GQA
    signature: (H + 2·H_kv)·Dh < 3·d_model when H_kv < H). Rank-
    agnostic on the LEADING dims: pipelined-LM checkpoints stack
    stage params ([S, …] / [v, S, …]), so kernels are 3-D/4-D there —
    only the trailing (in, out) pair is the layout signature."""
    found = False

    def visit(path, leaf):
        nonlocal found
        keys = [str(getattr(k, "key", k)) for k in path]
        if (
            "qkv" in keys
            and keys[-1] == "kernel"
            and getattr(leaf, "ndim", 0) >= 2
            and leaf.shape[-1] != 3 * leaf.shape[-2]
        ):
            found = True

    jax.tree_util.tree_map_with_path(visit, tree)
    return found


def _check_qkv_format(fmt: int | None, tree: Any, source: str) -> None:
    f = fmt or 1
    if f < 2 and _has_fused_qkv(tree):
        raise RuntimeError(
            f"{source} predates the head-major fused-qkv layout "
            f"(format {f} < {CHECKPOINT_FORMAT}) and contains "
            "attention weights — restoring it here would silently "
            "scramble q/k/v across heads (same shapes, different "
            "column order). Convert it once with "
            "scripts/convert_qkv_layout.py --num_heads <H>."
        )
    if f == 2 and _has_gqa_qkv(tree):
        raise RuntimeError(
            f"{source} holds grouped-query attention weights in the "
            "format-2 BLOCK layout ([q·H | k·H_kv | v·H_kv]); round 4 "
            "moved GQA to group-major columns so TP shards are whole "
            "kv groups — same shapes, different order, a silent "
            "restore would scramble attention. Convert it once with "
            "scripts/convert_qkv_layout.py --num_heads <H> "
            "--num_kv_heads <K>."
        )


# --- checkpoint integrity manifests -----------------------------------
#
# Orbax's commit protocol makes a *crash mid-save* atomic, but nothing
# defends the committed bytes afterwards: a torn copy, a truncated
# restore from object storage, bit rot, or a chaos drill
# (runtime/chaos.py ckpt_corrupt) leaves a "latest" that passes
# discovery and fails — or worse, silently corrupts — the restore.
# Every save therefore gets a sidecar manifest (``epoch_N.manifest.json``
# next to the step directory) listing each file's size and CRC-32;
# restore-time discovery verifies the latest manifest and, on mismatch,
# QUARANTINES the step directory (renamed aside, never deleted — it is
# evidence) and falls back to the previous intact epoch, so the
# auto-resume path recovers instead of crashing. CRC-32 is an
# integrity check against accidents, not an authenticity check against
# adversaries. Manifest-less epochs (pre-upgrade checkpoints, or a
# save whose process died before ``wait()``) are accepted unverified —
# integrity never makes old checkpoints unreadable.

MANIFEST_SUFFIX = ".manifest.json"
QUARANTINE_PREFIX = "quarantine."


def _crc32_file(path: str, chunk: int = 1 << 20) -> int:
    crc = 0
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk)
            if not block:
                return crc & 0xFFFFFFFF
            crc = zlib.crc32(block, crc)


def _manifest_path(root: str, epoch: int) -> str:
    return os.path.join(root, f"epoch_{epoch}{MANIFEST_SUFFIX}")


def build_manifest(step_dir: str) -> dict:
    """Walk a committed step directory → {relpath: {size, crc32}}."""
    files: dict[str, dict] = {}
    for dirpath, _, names in os.walk(step_dir):
        for name in sorted(names):
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, step_dir)
            files[rel] = {
                "size": os.path.getsize(path),
                "crc32": _crc32_file(path),
            }
    return {"version": 1, "files": files}


def write_manifest(root: str, epoch: int) -> str | None:
    """Manifest the committed ``epoch_<N>`` dir (atomic tmp+replace).
    Returns the manifest path, or None when the step dir is absent."""
    step_dir = os.path.join(root, f"epoch_{epoch}")
    if not os.path.isdir(step_dir):
        return None
    manifest = build_manifest(step_dir)
    path = _manifest_path(root, epoch)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(manifest, f, sort_keys=True)
    os.replace(tmp, path)
    return path


def verify_manifest(root: str, epoch: int) -> list[str] | None:
    """Check ``epoch_<N>`` against its manifest.

    Returns ``None`` when no (readable) manifest exists — the epoch is
    UNVERIFIABLE and accepted for compatibility; ``[]`` when every
    listed file matches; otherwise a list of human-readable problems
    (missing file / size mismatch / checksum mismatch). Files present
    on disk but absent from the manifest are ignored — descriptors and
    later tooling may legitimately add them.
    """
    path = _manifest_path(root, epoch)
    try:
        with open(path) as f:
            manifest = json.load(f)
        listed = dict(manifest["files"])
    except (OSError, ValueError, KeyError, TypeError):
        return None
    step_dir = os.path.join(root, f"epoch_{epoch}")
    problems: list[str] = []
    for rel, meta in sorted(listed.items()):
        p = os.path.join(step_dir, rel)
        try:
            size = os.path.getsize(p)
        except OSError:
            problems.append(f"{rel}: missing")
            continue
        if size != meta.get("size"):
            problems.append(
                f"{rel}: size {size} != manifest {meta.get('size')}"
            )
            continue
        if _crc32_file(p) != meta.get("crc32"):
            problems.append(f"{rel}: checksum mismatch")
    return problems


# --- LM spec sidecar --------------------------------------------------
#
# The architecture fields an LM checkpoint's shapes cannot carry —
# head count, MoE routing (top_k, gate normalization), sequence
# strategy — ride next to the checkpoints as one JSON file, like the
# tokenizer does (trainer writes ``tokenizer.json`` beside the epochs).
# Inference tooling (scripts/predict.py, scripts/serve.py) merges it
# over the shape-derived spec (models/lm.py derive_lm_spec), so a
# checkpoint trained at --moe_top_k 1 serves with top-1 routing
# instead of silently assuming the top-2 default.

LM_SPEC_FILENAME = "lm_spec.json"


def save_lm_spec(directory: str, spec: Any) -> str:
    """Write ``spec`` (an LMSpec) as JSON beside the checkpoints."""
    import json

    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, LM_SPEC_FILENAME)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(dict(spec._asdict()), f, indent=2, sort_keys=True)
    os.replace(tmp, path)  # atomic like the checkpoint commits
    return path


def save_params_with_spec(directory: str, spec: Any, params: Any, *,
                          epoch: int = 0) -> None:
    """A parameter tree as a checkpoint inference tooling restores
    (``epoch_N/`` with an empty optimizer state) plus its ``lm_spec.json``
    sidecar: what the serving-only models' ``save_checkpoint`` write."""
    mgr = CheckpointManager(directory, async_save=False)
    mgr.save(epoch, TrainState(
        step=np.zeros((), np.int32), params=params, opt_state={},
        model_state={},
    ))
    mgr.close()
    save_lm_spec(directory, spec)


def load_lm_spec_fields(directory: str) -> dict:
    """Read the sidecar → field dict ({} when absent or unreadable).

    Returns a plain dict (not an LMSpec) filtered to the fields the
    CURRENT LMSpec knows, so older/newer sidecars degrade to whatever
    subset still applies instead of failing construction.
    """
    import json

    from ddp_tpu.models.lm import LMSpec

    path = os.path.join(directory, LM_SPEC_FILENAME)
    try:
        with open(path) as f:
            fields = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(fields, dict):
        return {}
    # a JSON array is a spec's tuple (``layer_types``): a spec is hashable
    return {
        k: tuple(v) if isinstance(v, list) else v
        for k, v in fields.items() if k in LMSpec._fields
    }


def derive_spec_with_sidecar(
    directory: str, params: Any, *, num_heads_fallback: int
):
    """Restored params + ``lm_spec.json`` sidecar → LMSpec.

    The shared inference-tooling recipe (scripts/predict.py,
    scripts/serve.py): shapes are ground truth, the sidecar supplies
    what they cannot carry (head count, MoE routing, strategy), and
    ``num_heads_fallback`` (a CLI flag) covers sidecar-less
    checkpoints. Raises ValueError when the tree is not a causal-LM
    tree or the head count does not explain the shapes.
    """
    from ddp_tpu.models.lm import derive_lm_spec

    sidecar = load_lm_spec_fields(directory)
    return derive_lm_spec(
        params,
        num_heads=sidecar.pop("num_heads", num_heads_fallback),
        **sidecar,
    )


# --- elastic world-resize contract ------------------------------------
#
# The one fact an elastic relaunch cannot re-derive from its own flags:
# the ORIGINAL global batch size. Config flags are per-shard
# (``--batch_size`` × live shards), so a shrunk world would silently
# halve the global batch — changing what a step means, desynchronizing
# the checkpointed step counter from the LR schedule and the
# steps-per-epoch the mid-epoch resume markers were written under. The
# first generation records the contract once; every later generation
# rescales its per-shard batch to honor it
# (data/sampler.rescale_per_shard_batch). Write-once on purpose: the
# contract is the run's invariant, not the latest generation's shape.

ELASTIC_FILENAME = "elastic.json"


def save_elastic_contract(
    directory: str, *, global_batch_size: int, world_size: int
) -> str | None:
    """Record the run's global-batch contract (first generation only —
    an existing contract is never overwritten). Returns the path, or
    None when a contract already existed."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, ELASTIC_FILENAME)
    if os.path.exists(path):
        return None
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(
            {
                "global_batch_size": int(global_batch_size),
                "world_size": int(world_size),
            },
            f,
        )
    os.replace(tmp, path)
    return path


def load_elastic_contract(directory: str) -> dict:
    """The recorded contract, or {} (first generation / non-elastic
    run / unreadable sidecar — all mean "no rescale to honor")."""
    path = os.path.join(directory, ELASTIC_FILENAME)
    try:
        with open(path) as f:
            contract = json.load(f)
    except (OSError, ValueError):
        return {}
    return contract if isinstance(contract, dict) else {}


class CheckpointManager:
    """Per-epoch checkpoints with latest-epoch auto-resume.

    ``last_restored_spe`` / ``last_restored_mid_batch`` hold what the
    most recently restored checkpoint recorded (None / 0 for legacy
    checkpoints): the steps-per-epoch it was written under, and how
    many batches into its tagged epoch the state is (0 = the epoch
    completed). The trainer uses the pair to re-enter a preempted
    epoch at the exact batch — an explicit marker, not step-counter
    arithmetic, so imported checkpoints with foreign step offsets
    (scripts/import_torch_checkpoint.py) can never alias a mid-epoch
    position.
    """

    last_restored_spe: int | None = None
    last_restored_mid_batch: int = 0

    def __init__(
        self,
        directory: str = "./checkpoints",
        *,
        max_to_keep: int | None = None,
        async_save: bool = True,
        keep_best_metric: str | None = None,
    ):
        """``keep_best_metric``: retain the ``max_to_keep`` checkpoints
        with the HIGHEST value of that metric (passed to ``save``) PLUS
        the chronologically latest one — best-N alone would delete the
        newest checkpoint whenever it underperforms, silently breaking
        latest-epoch auto-resume (restarts would re-train completed
        epochs). Saves without metrics (preemption artifacts) are
        always preserved.
        """
        self._dir = os.path.abspath(directory)
        opts_kwargs: dict = dict(
            max_to_keep=None if keep_best_metric else max_to_keep,
            create=True,
            enable_async_checkpointing=async_save,
            step_prefix="epoch",
        )
        if keep_best_metric:
            from orbax.checkpoint.checkpoint_managers import (
                AnyPreservationPolicy,
                BestN,
                LatestN,
            )

            opts_kwargs["preservation_policy"] = AnyPreservationPolicy(
                [
                    LatestN(1),  # auto-resume anchor
                    BestN(
                        get_metric_fn=lambda m: m[keep_best_metric],
                        # reverse=False keeps the HIGHEST metric
                        # values (empirically: reverse=True retains
                        # the lowest)
                        reverse=False,
                        n=max_to_keep,
                        keep_checkpoints_without_metrics=True,
                    ),
                ]
            )
        opts = ocp.CheckpointManagerOptions(**opts_kwargs)
        # Explicit handler so item_metadata works before any save/
        # restore call registered one (the template-free inference path
        # in a fresh process).
        self._opts = opts
        self._mgr = ocp.CheckpointManager(
            self._dir, options=opts,
            item_handlers=ocp.StandardCheckpointHandler(),
        )
        # Integrity bookkeeping: epochs saved but not yet manifested
        # (async saves aren't durable until committed — manifests are
        # written at the next wait()/save()), and what THIS process
        # quarantined (the trainer surfaces these as fallback events).
        self._manifest_pending: set[int] = set()
        self.quarantined: list[dict] = []

    @property
    def directory(self) -> str:
        return self._dir

    def latest_epoch(self) -> int | None:
        """Discovery: the reference's "latest file in ./checkpoints"."""
        return self._mgr.latest_step()

    # ---- integrity: manifests, verification, quarantine --------------

    @staticmethod
    def _is_manifest_writer() -> bool:
        # One writer per world: every process shares the filesystem in
        # single-host spawns, and concurrent identical writes would
        # only race on the rename.
        return jax.process_index() == 0

    def _flush_manifests(self) -> None:
        """Write manifests for pending epochs that are now COMMITTED.

        Commit is detected by the final ``epoch_<N>`` directory
        existing — NOT by ``all_steps()``, which orbax populates
        optimistically at ``save()`` time while an async save is still
        writing into its ``...orbax-checkpoint-tmp-...`` directory
        (the atomic rename to ``epoch_<N>`` is the commit point).
        Cheap to call opportunistically; in-flight saves simply stay
        pending until ``wait()``/``close()``.
        """
        for epoch in sorted(self._manifest_pending):
            if not os.path.isdir(
                os.path.join(self._dir, f"epoch_{epoch}")
            ):
                continue  # async save not yet committed
            self._manifest_pending.discard(epoch)
            if not self._is_manifest_writer():
                continue
            try:
                write_manifest(self._dir, epoch)
            except OSError as e:  # integrity is best-effort, never fatal
                logger.warning(
                    "manifest write for epoch %d failed: %s", epoch, e
                )
        self._sweep_orphan_manifests()

    def _sweep_orphan_manifests(self) -> None:
        """Remove sidecars whose epoch Orbax's own retention deleted.

        ``max_to_keep`` and the keep-best preservation policy garbage-
        collect ``epoch_<N>`` directories inside Orbax, which knows
        nothing of our ``epoch_<N>.manifest.json`` beside them. A
        manifest with no directory verifies nothing and reads as a
        kept checkpoint to anything that lists the directory. Epochs
        still pending (async save in flight: no final directory YET)
        are left alone.
        """
        if not self._is_manifest_writer():
            return
        try:
            names = os.listdir(self._dir)
        except OSError:
            return
        for name in names:
            if not (
                name.startswith("epoch_") and name.endswith(MANIFEST_SUFFIX)
            ):
                continue
            stem = name[: -len(MANIFEST_SUFFIX)]
            if not stem[len("epoch_"):].isdigit():
                continue
            if int(stem[len("epoch_"):]) in self._manifest_pending:
                continue
            if not os.path.isdir(os.path.join(self._dir, stem)):
                try:
                    os.remove(os.path.join(self._dir, name))
                except OSError:
                    pass

    def _drop_manifest(self, epoch: int) -> None:
        self._manifest_pending.discard(epoch)
        try:
            os.remove(_manifest_path(self._dir, epoch))
        except OSError:
            pass

    def _delete_epoch(self, epoch: int) -> None:
        self._mgr.delete(epoch)
        self._drop_manifest(epoch)

    def _reload_steps(self) -> None:
        """Refresh the manager's step view after an out-of-band rename
        (quarantine). ``reload()`` re-scans the directory — safe
        because quarantined names use a DASH (``quarantine.epoch-N``):
        orbax's step scanner splits names on "_", so an underscore
        name would still parse as step N and the re-scan would then
        fail to find its directory. Deliberately not a manager
        rebuild: ``CheckpointManager.__init__``/``close()`` are not
        process-symmetric-safe, and only SOME ranks reload (a one-rank
        rebuild deadlocked the multi-process resume)."""
        self._mgr.reload()

    def verify_epoch(self, epoch: int) -> list[str] | None:
        """Manifest check → problems ([] ok, None unverifiable)."""
        return verify_manifest(self._dir, epoch)

    def quarantine_epoch(self, epoch: int, problems: list[str]) -> str | None:
        """Rename a corrupt epoch ASIDE (never delete — it is the
        post-mortem evidence) so discovery stops seeing it; its
        manifest moves inside the quarantined directory. Concurrent
        ranks race benignly: the loser's rename fails and the epoch is
        already gone. Returns the quarantine path (None if a peer got
        there first)."""
        src = os.path.join(self._dir, f"epoch_{epoch}")
        # Dash, not underscore: orbax's step scanner splits names on
        # "_", so "quarantine.epoch_1" would still parse as step 1 —
        # the quarantined name must not contain an epoch_<N> token.
        dst = os.path.join(
            self._dir, f"{QUARANTINE_PREFIX}epoch-{epoch}"
        )
        n = 0
        while os.path.exists(dst):
            n += 1
            dst = os.path.join(
                self._dir, f"{QUARANTINE_PREFIX}epoch-{epoch}.{n}"
            )
        try:
            os.rename(src, dst)
        except OSError:
            dst = None  # a peer rank quarantined it first
        else:
            try:
                os.replace(
                    _manifest_path(self._dir, epoch),
                    os.path.join(dst, "ddp_tpu" + MANIFEST_SUFFIX),
                )
            except OSError:
                pass
            logger.error(
                "Checkpoint epoch %d failed integrity verification "
                "(%s) — quarantined to %s; falling back to the "
                "previous intact checkpoint",
                epoch, "; ".join(problems) or "unknown", dst,
            )
        self._manifest_pending.discard(epoch)
        self.quarantined.append(
            {"epoch": epoch, "path": dst, "problems": list(problems)}
        )
        self._reload_steps()
        return dst

    def latest_intact_epoch(self) -> int | None:
        """Latest epoch that passes integrity verification, walking
        backwards past (and quarantining) corrupt ones. Manifest-less
        epochs are accepted unverified. None when nothing usable is
        left.

        Multi-process: only process 0 verifies and quarantines —
        peers would multiply the CRC read of a multi-GB checkpoint by
        world size and race the quarantine renames. A barrier pairs
        the two sides (every process calls it exactly once), so peers
        read the post-quarantine view; this also sequences rank 0's
        process-start chaos (``ckpt_corrupt``) before any peer's
        discovery. Assumes the checkpoint dir is one shared (local/
        NFS) filesystem, like every sidecar here.
        """
        multi = jax.process_count() > 1

        def barrier():
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("ckpt_integrity_verify")

        if multi and jax.process_index() != 0:
            barrier()
            self._reload_steps()  # see process 0's quarantine renames
            return self._mgr.latest_step()
        try:
            while True:
                epoch = self._mgr.latest_step()
                if epoch is None:
                    return None
                problems = self.verify_epoch(epoch)
                if not problems:  # [] verified-ok, or None unverifiable
                    return epoch
                if self.quarantine_epoch(epoch, problems) is None and (
                    epoch == self._mgr.latest_step()
                ):
                    # The rename failed AND the epoch is still visible
                    # (read-only dir, not a peer's racing quarantine):
                    # looping would verify the same bytes forever.
                    raise RuntimeError(
                        f"checkpoint epoch {epoch} fails integrity "
                        f"verification ({'; '.join(problems)}) and "
                        f"cannot be quarantined — is {self._dir} "
                        "writable?"
                    )
        finally:
            # Process 0 reaches this on EVERY exit (including the
            # raise above — peers then fail on their own rather than
            # hanging in a barrier no one will join).
            if multi:
                barrier()

    def all_epochs(self) -> list[int]:
        """Every saved epoch tag, ascending."""
        return sorted(self._mgr.all_steps() or [])

    def metadata(self, epoch: int) -> dict:
        """Shape/dtype metadata tree for one epoch (no array reads)."""
        return dict(self._mgr.item_metadata(epoch))

    def save(
        self,
        epoch: int,
        state: TrainState,
        *,
        overwrite: bool = False,
        steps_per_epoch: int = 0,
        mid_batch: int = 0,
        metrics: dict | None = None,
    ) -> bool:
        """Save ``{params, opt_state, step}`` for ``epoch``.

        Collective: every process calls it; Orbax elects writers — the
        multi-host-safe version of the reference's ``if rank == 0:
        torch.save(...)`` (train_ddp.py:204).

        Same-epoch conflicts (a mid-epoch preemption artifact already
        holds this tag): with ``overwrite=False`` the save is skipped —
        the old artifact stays valid and the NEXT epoch's save
        supersedes it, so no crash window ever leaves the directory
        without a usable latest. ``overwrite=True`` (preemption saves
        replacing an older same-epoch artifact) deletes then saves;
        a crash inside that window falls back to the previous epoch —
        recompute, never corruption.
        """
        if epoch in (self._mgr.all_steps() or []):
            if not overwrite:
                logger.info(
                    "Checkpoint for epoch %d already exists (preemption "
                    "artifact) — keeping it; a later save supersedes it",
                    epoch,
                )
                return False
            self._delete_epoch(epoch)
        # steps_per_epoch and the explicit mid-epoch batch position ride
        # along so resume needs no step-counter arithmetic (which a
        # changed config or an imported foreign checkpoint would break);
        # mid_batch 0 means the tagged epoch completed.
        tree = dict(
            state._asdict(),
            # 0-d arrays, not numpy scalars: older orbax
            # StandardCheckpointHandlers reject np.int32(...) leaves.
            spe=np.asarray(steps_per_epoch, np.int32),
            mid_batch=np.asarray(mid_batch, np.int32),
            fmt=np.asarray(CHECKPOINT_FORMAT, np.int32),
        )
        self._mgr.save(
            epoch, args=ocp.args.StandardSave(tree), metrics=metrics
        )
        # Integrity manifest: pending until the (possibly async) save
        # commits — flushed opportunistically now (earlier saves have
        # committed by this point) and at wait()/close().
        self._manifest_pending.add(epoch)
        self._flush_manifests()
        return True

    def restore(
        self,
        state_like: TrainState,
        epoch: int | None = None,
        *,
        opt_reshape=None,
    ) -> tuple[TrainState, int]:
        """Restore → (state, epoch). ``state_like`` supplies the tree
        structure/shardings (its values are discarded).

        ``epoch=None`` runs verified discovery: corrupt/truncated
        epochs are quarantined and discovery falls back to the
        previous intact one (``latest_intact_epoch``). An EXPLICIT
        epoch that fails verification raises instead — the caller
        named that state on purpose; silently substituting another
        would be worse than failing.

        ``opt_reshape`` makes the restore world-shape-agnostic for
        optimizer states whose GLOBAL shapes depend on the world size
        (the zero strategy's padded flat buckets,
        parallel/zero.ZeroElasticReshaper). Protocol: ``plan(meta)``
        receives the checkpoint's opt_state shape metadata and returns
        either None (shapes match the live template — the ordinary
        templated restore runs, resharding on load) or an abstract
        tree in the SAVED shapes; ``apply(restored)`` then converts the
        old-world values into the live layout. Params/step/model_state
        always restore templated on the live shardings — that half is
        reshard-on-load by construction (tests/test_elastic_shard.py).
        """
        if epoch is None:
            epoch = self.latest_intact_epoch()
            if epoch is None:
                raise FileNotFoundError(f"no checkpoints in {self._dir}")
        else:
            problems = self.verify_epoch(epoch)
            if problems:
                raise RuntimeError(
                    f"checkpoint epoch {epoch} fails integrity "
                    f"verification: {'; '.join(problems)} — restore a "
                    "different epoch, or delete its manifest to force "
                    "an unverified read"
                )
        abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, state_like._asdict())
        abstract["spe"] = jax.ShapeDtypeStruct((), np.int32)
        abstract["mid_batch"] = jax.ShapeDtypeStruct((), np.int32)
        abstract["fmt"] = jax.ShapeDtypeStruct((), np.int32)
        reshape_apply = None
        if opt_reshape is not None:
            try:
                meta_opt = dict(self._mgr.item_metadata(epoch)).get(
                    "opt_state"
                )
            except (OSError, ValueError, KeyError, TypeError):
                meta_opt = None  # legacy/partial checkpoint: restore as-is
            if meta_opt is not None:
                override = opt_reshape.plan(meta_opt)
                if override is not None:
                    abstract["opt_state"] = override
                    reshape_apply = opt_reshape.apply
                    logger.warning(
                        "Checkpoint epoch %d holds optimizer state "
                        "bucketed for a different world size — "
                        "re-bucketing on restore (elastic resize)",
                        epoch,
                    )
        # Migration ladder: older checkpoints lack "fmt" (and before
        # that "mid_batch", "spe", "model_state"); retry dropping the
        # optional keys oldest-format-last.
        ladder = (
            (),
            ("fmt",),
            ("fmt", "mid_batch"),
            ("fmt", "mid_batch", "spe"),
            ("fmt", "mid_batch", "spe", "model_state"),
        )
        for drop in ladder:
            attempt = {k: v for k, v in abstract.items() if k not in drop}
            try:
                restored = dict(
                    self._mgr.restore(
                        epoch, args=ocp.args.StandardRestore(attempt)
                    )
                )
                break
            except (ValueError, KeyError):
                if drop == ladder[-1]:
                    raise
        if reshape_apply is not None and "opt_state" in restored:
            restored["opt_state"] = reshape_apply(restored["opt_state"])
        restored.setdefault("model_state", state_like.model_state)
        fmt = int(restored.pop("fmt", 1))
        _check_qkv_format(
            fmt, restored["params"], f"checkpoint epoch {epoch}"
        )
        self.last_restored_spe = int(restored.pop("spe", 0)) or None
        if "mid_batch" in restored:
            self.last_restored_mid_batch = int(restored.pop("mid_batch"))
        elif self.last_restored_spe:
            # Pre-mid_batch checkpoint: its intra-epoch position is
            # encoded only in the step counter (the old scheme, valid
            # because nothing but the trainer ever wrote that format).
            self.last_restored_mid_batch = (
                int(restored["step"]) % self.last_restored_spe
            )
        else:
            self.last_restored_mid_batch = 0
        return TrainState(**restored), epoch

    def delete_after(self, epoch: int) -> list[int]:
        """Delete every checkpoint tagged LATER than ``epoch``.

        The rewind contract (``--resume_epoch``): the branch being
        abandoned must not survive as "latest", or a crash in the
        rewound run would auto-resume exactly the state the user chose
        to discard. Returns the deleted tags.
        """
        stale = sorted(e for e in (self._mgr.all_steps() or []) if e > epoch)
        for e in stale:
            self._delete_epoch(e)
        return stale

    _pytree_mgr = None

    def read_partial(self, epoch: int, keys: tuple[str, ...]) -> dict:
        """Read ONLY ``keys`` of a checkpoint, topology-independent.

        The abstract tree comes from the checkpoint's own metadata (no
        model/optimizer construction); explicit single-device shardings
        replace the recorded ones, which reference the topology the
        checkpoint was WRITTEN under (e.g. an 8-device emulated mesh)
        and cannot deserialize elsewhere. Skipped entries pay no I/O
        (``partial_restore`` — an Adam opt_state is 2× the params).
        """
        meta = dict(self._mgr.item_metadata(epoch))
        wanted = {k: meta[k] for k in keys if k in meta}
        return self._restore_subtree(epoch, wanted)

    def _restore_subtree(self, epoch: int, wanted: dict) -> dict:
        """Restore exactly the metadata subtree ``wanted`` (any
        nesting depth) with single-device shardings — the shared tail
        of ``read_partial`` and ``read_params_children``."""
        dev = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
        abstract = jax.tree.map(
            lambda m: jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=dev),
            wanted,
        )
        restore_args = jax.tree.map(
            lambda _: ocp.ArrayRestoreArgs(sharding=dev), abstract
        )
        if self._pytree_mgr is None:
            # The main manager is registered for the Standard handler;
            # partial restore needs the PyTree one. One lazy instance
            # serves every read (scripts iterate all epochs).
            self._pytree_mgr = ocp.CheckpointManager(
                self._dir,
                options=ocp.CheckpointManagerOptions(step_prefix="epoch"),
                item_handlers=ocp.PyTreeCheckpointHandler(),
            )
        args = ocp.args.PyTreeRestore(
            item=abstract,
            restore_args=restore_args,
            partial_restore=True,
        )
        return dict(self._pytree_mgr.restore(epoch, args=args))

    def params_metadata(self, epoch: int):
        """Shape/dtype metadata of the checkpoint's ``params`` entry —
        NO tensor data is read. The leaves carry ``.shape``/``.dtype``
        like arrays do, so ``models/lm.derive_lm_spec`` runs on the
        metadata tree directly: streaming restore
        (serve/lifecycle.py) derives the engine spec and starts
        compiling before a single weight byte arrives."""
        meta = dict(self._mgr.item_metadata(epoch))
        if "params" not in meta:
            raise KeyError(
                f"checkpoint epoch {epoch} has no params entry"
            )
        return meta["params"]

    def read_params_children(
        self, epoch: int, names: Sequence[str]
    ) -> dict:
        """Restore ONLY the named top-level children of ``params``.

        The streaming-restore primitive (serve/lifecycle.py): the
        embedding + first-K-blocks group restores and opens admission
        while the deep blocks are still in flight on a second call.
        Unknown names are skipped (the group splitter works from the
        same metadata, so a miss means a racing rewrite — the caller's
        residency check catches it). Returns ``{child: tree}``.
        """
        params_meta = self.params_metadata(epoch)
        sel = {k: params_meta[k] for k in names if k in params_meta}
        if not sel:
            return {}
        restored = self._restore_subtree(epoch, {"params": sel})
        return dict(restored["params"])

    def restore_for_inference(
        self, epoch: int | None = None
    ) -> tuple[Any, Any, int]:
        """Template-free restore → ``(params, model_state, epoch)``.

        Inference tooling (scripts/predict.py) loads ANY run's
        checkpoint without knowing which optimizer produced it; the
        optimizer state is never read. Discovery is integrity-verified
        like ``restore`` (corrupt latest → quarantine + fall back).
        """
        if epoch is None:
            epoch = self.latest_intact_epoch()
            if epoch is None:
                raise FileNotFoundError(f"no checkpoints in {self._dir}")
        else:
            problems = self.verify_epoch(epoch)
            if problems:
                raise RuntimeError(
                    f"checkpoint epoch {epoch} fails integrity "
                    f"verification: {'; '.join(problems)}"
                )
        restored = self.read_partial(epoch, ("params", "model_state", "fmt"))
        fmt = restored.pop("fmt", None)
        _check_qkv_format(
            int(fmt) if fmt is not None else None,
            restored["params"],
            f"checkpoint epoch {epoch}",
        )
        return restored["params"], restored.get("model_state", {}), epoch

    def restore_or_init(
        self, state: TrainState, *, opt_reshape=None
    ) -> tuple[TrainState, int]:
        """The auto-resume entry: (state, start_epoch).

        Mirrors train_ddp.py:49-89's flag dance — resume from latest
        epoch + 1 when a checkpoint exists, else epoch 0 fresh.
        ``opt_reshape`` passes through to ``restore`` (the elastic
        world-resize hook).
        """
        # Single-process only: multi-process ranks may reach this
        # pre-check at different times relative to process 0's
        # quarantine renames, and a rank that short-circuits here
        # would skip the verification barrier its peers are blocked
        # in. Multi-process ALWAYS enters restore() (the barrier
        # pairs), and "nothing usable" surfaces as FileNotFoundError
        # on every rank consistently.
        if jax.process_count() == 1 and self.latest_epoch() is None:
            logger.info("No checkpoint found — starting from scratch")
            return state, 0
        try:
            # epoch=None → verified discovery with quarantine fallback.
            restored, epoch = self.restore(
                state, None, opt_reshape=opt_reshape
            )
        except FileNotFoundError:
            # Nothing to restore — either the directory is empty, or
            # EVERY checkpoint failed verification and was quarantined
            # (recompute beats restoring corruption, and the
            # quarantined evidence survives for the post-mortem).
            logger.warning(
                "No intact checkpoint in %s (%d quarantined) — "
                "starting from scratch",
                self._dir, len(self.quarantined),
            )
            return state, 0
        logger.info("Resumed from checkpoint epoch %d", epoch)
        return restored, epoch + 1

    def wait(self) -> None:
        """Block until async saves are durable (call before exit);
        durable saves then get their integrity manifests."""
        self._mgr.wait_until_finished()
        self._flush_manifests()

    def close(self) -> None:
        self._mgr.wait_until_finished()
        self._flush_manifests()
        self._mgr.close()
        if self._pytree_mgr is not None:
            self._pytree_mgr.close()
            self._pytree_mgr = None


imported(__name__, _IMPORT_T0)
