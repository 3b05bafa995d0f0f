"""Device-mesh construction — the scale-out geometry of the framework.

The reference's only geometry is a flat rank list (world_size processes,
c10d communicator over all of them). The TPU-native shape is an N-D
``jax.sharding.Mesh`` whose axes name the parallelism strategies; XLA
lowers collectives onto ICI (intra-slice) / DCN (inter-slice) from axis
placement alone.

Axis vocabulary (fixed across the framework):

- ``dcn``    — the SLICE axis of a multi-slice pod: groups of chips
               joined by the slow inter-slice data-center network
               rather than ICI. Outermost by construction, so the
               flattened device order keeps each slice's chips in one
               contiguous block (replica-group ids stay slice-local —
               what the HLO comm cross-check keys on). A data axis for
               batch sharding; the hierarchical zero step reduces
               within a slice over ICI and exchanges only 1/N shards
               across slices over this axis (PAPERS.md #5).
- ``data``   — data parallelism: batch sharded, params replicated,
               gradient all-reduce (the reference's entire capability,
               SURVEY.md §2c).
- ``fsdp``   — parameter/optimizer sharding (ZeRO-style) on top of data
               parallelism.
- ``expert`` — expert parallelism: MoE expert weights shard their
               leading (expert) dim; tokens shard their batch dim over
               this axis too, so it doubles as a data axis for dense
               layers (GShard-style).
- ``model``  — tensor parallelism within layers.
- ``seq``    — sequence/context parallelism (ring attention).
- ``pipe``   — pipeline stages.

A 1-D ``('data',)`` mesh over all chips reproduces DDP exactly; the
other axes exist so the same train step scales without restructuring.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Sequence

import numpy as np

AXIS_ORDER = ("dcn", "pipe", "data", "fsdp", "expert", "seq", "model")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape; -1 on at most one axis means "all the rest".

    ``MeshSpec()`` (all defaults) is pure DDP: every device on ``data``.
    """

    data: int = -1
    fsdp: int = 1
    expert: int = 1
    model: int = 1
    seq: int = 1
    pipe: int = 1
    dcn: int = 1

    def resolve(self, num_devices: int) -> dict[str, int]:
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        bad = {a: s for a, s in sizes.items() if s < 1 and s != -1}
        if bad:
            raise ValueError(
                f"mesh axis sizes must be ≥ 1 (or -1 for 'the rest'): {bad}"
            )
        unknown = [a for a, s in sizes.items() if s == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one -1 axis, got {unknown}")
        known = math.prod(s for s in sizes.values() if s != -1)
        if unknown:
            if num_devices % known:
                raise ValueError(
                    f"{num_devices} devices not divisible by fixed axes {sizes}"
                )
            sizes[unknown[0]] = num_devices // known
        elif known != num_devices:
            raise ValueError(f"mesh {sizes} needs {known} devices, have {num_devices}")
        return sizes


def detect_slices(devices: Sequence) -> int:
    """Number of distinct pod slices among ``devices``.

    Real multi-slice TPU pods expose ``slice_index`` per device; CPU
    emulation (and single-slice pods) have none, which reads as one
    slice. The ``--mesh_dcn`` flag stays explicit — this is the
    auto-detection input, not a policy.
    """
    idx = {getattr(d, "slice_index", None) for d in devices}
    idx.discard(None)
    return max(1, len(idx))


def _slice_major(devices: Sequence, n_slices: int) -> list:
    """Order devices so equal-slice groups are contiguous (the ``dcn``
    axis is outermost, so a plain reshape then maps each slice to one
    dcn index). Real pods group by ``slice_index``; emulated worlds
    (multi-process gloo "slices") group by process — jax device order
    is already process-major there, so the sort is stable either way.

    When the devices carry real ``slice_index`` info it must AGREE
    with ``n_slices``: on (say) a 4-slice pod, ``--mesh_dcn 2`` would
    silently build a mesh whose "ICI" axis spans two real slices —
    the heavy within-slice collectives would ride the slow fabric and
    the ici/dcn attribution would report them on the wrong side. That
    misconfiguration is rejected with both numbers named; uneven
    slices are rejected too.
    """
    detected = detect_slices(devices)
    # slice_index is AUTHORITATIVE on real accelerators (a single-
    # slice pod genuinely has one slice — asking for --mesh_dcn 2
    # there would stamp within-slice ICI traffic as DCN, rejected
    # below). CPU devices report the same degenerate one-slice shape
    # but mean "no fabric at all": there the flag is an EMULATION and
    # slices group by process instead.
    has_slice_info = any(
        getattr(d, "slice_index", None) is not None for d in devices
    )
    real_slices = has_slice_info and (
        detected > 1 or getattr(devices[0], "platform", "cpu") != "cpu"
    )
    if real_slices and detected != n_slices:
        raise ValueError(
            f"--mesh_dcn {n_slices} but the devices report {detected} "
            "slice(s) (slice_index) — the dcn axis must match the "
            "physical slice count or the within-slice collectives "
            "silently cross the slow fabric"
        )
    order = sorted(
        range(len(devices)),
        key=lambda i: (
            getattr(devices[i], "slice_index", None)
            if real_slices
            and getattr(devices[i], "slice_index", None) is not None
            else getattr(devices[i], "process_index", 0),
            i,
        ),
    )
    devs = [devices[i] for i in order]
    per = len(devs) // n_slices
    if real_slices:
        counts: dict = {}
        for d in devs:
            k = getattr(d, "slice_index", 0)
            counts[k] = counts.get(k, 0) + 1
        if len(set(counts.values())) > 1 or per * n_slices != len(devs):
            raise ValueError(
                f"--mesh_dcn {n_slices}: device slices are uneven "
                f"({counts}) — every slice must contribute the same "
                "chip count"
            )
    else:
        # Emulated slices group by process. A slice may span WHOLE
        # processes (a real slice holds many hosts), but a single
        # process split ACROSS slice blocks would put "within-slice"
        # collectives on the very process boundary the emulation calls
        # DCN — the math would still be right, the ici/dcn attribution
        # (records, per-axis xprof check, the hier bench claim) wrong.
        procs = [getattr(d, "process_index", 0) for d in devs]
        if len(set(procs)) > 1:
            for p in set(procs):
                blocks = {
                    i // per
                    for i, proc in enumerate(procs)
                    if proc == p
                }
                if len(blocks) > 1:
                    raise ValueError(
                        f"--mesh_dcn {n_slices}: process {p}'s devices "
                        f"span emulated slice blocks ({len(devs)} "
                        f"devices / {len(set(procs))} processes do not "
                        f"tile {n_slices} slices) — each process must "
                        "sit inside one slice; adjust --spawn/"
                        "--emulate_devices or --mesh_dcn"
                    )
    return devs


def make_mesh(
    spec: MeshSpec | Mapping[str, int] | None = None,
    *,
    devices: Sequence | None = None,
):
    """Build a ``jax.sharding.Mesh`` from a logical spec.

    ``mesh_utils.create_device_mesh`` lays the axes on the physical
    ICI torus on TPU (innermost axes get the nearest chips) and is a
    plain reshape elsewhere; a topology it cannot lay the mesh on
    raises. A ``dcn`` axis > 1 orders devices slice-major first
    (``slice_index`` on real pods, process on emulated worlds) so the
    outermost axis genuinely separates the DCN fabric.
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if spec is None:
        spec = MeshSpec()
    elif isinstance(spec, Mapping):
        spec = MeshSpec(**dict(spec))
    sizes = spec.resolve(len(devices))
    shape = tuple(sizes[a] for a in AXIS_ORDER)

    if sizes["dcn"] > 1:
        devices = _slice_major(devices, sizes["dcn"])
        per = len(devices) // sizes["dcn"]
        # Torus-aware layout per slice, stacked along dcn — ICI
        # adjacency is a within-slice property.
        mesh_devices = np.stack(
            [
                mesh_utils.create_device_mesh(
                    shape[1:], devices=devices[i * per : (i + 1) * per]
                )
                for i in range(sizes["dcn"])
            ]
        )
        return Mesh(mesh_devices, AXIS_ORDER)
    return Mesh(
        mesh_utils.create_device_mesh(shape, devices=devices), AXIS_ORDER
    )


def live_world_spec(
    spec: MeshSpec | Mapping[str, int], num_devices: int
) -> MeshSpec:
    """Re-derive the data axis from the LIVE world (elastic restart).

    An elastic relaunch (runtime/launch.py ``elastic=True``) brings up
    however many workers survived — the mesh cannot be the config's
    mesh, it must be *this* world's. The fixed (non-data) axes are the
    model's sharding contract and survive the resize unchanged; the
    data axis absorbs whatever device count is actually present. Raises
    with the resize named when the fixed axes no longer tile the shrunk
    world (e.g. ``--mesh_model 4`` after dropping to 2 devices) — that
    topology genuinely cannot run, and the supervisor's ``min_world``
    is the knob that prevents reaching it.
    """
    if isinstance(spec, Mapping):
        spec = MeshSpec(**dict(spec))
    fixed = {
        a: getattr(spec, a) for a in AXIS_ORDER if a != "data"
    }
    bad = {a: s for a, s in fixed.items() if s < 1}
    if bad:
        raise ValueError(
            f"elastic resize: fixed mesh axes must be explicit (>= 1), "
            f"got {bad} — only the data axis may be world-derived"
        )
    # One owner for the tiling arithmetic: resolve() already absorbs
    # the -1 axis and rejects indivisible device counts — this wrapper
    # only adds the resize framing to the failure.
    try:
        sizes = dataclasses.replace(spec, data=-1).resolve(num_devices)
    except ValueError as e:
        raise ValueError(
            f"elastic resize: {num_devices} live device(s) cannot carry "
            f"the fixed mesh axes {fixed}; the data axis must absorb an "
            "integer multiple — scale --min_world (or the fixed axes) "
            f"so every reachable world tiles ({e})"
        ) from e
    return dataclasses.replace(spec, data=sizes["data"])


def data_axes(mesh) -> tuple[str, ...]:
    """Axes over which the batch is sharded and grads are averaged.

    ``dcn`` (slice groups each see different data), ``fsdp`` and
    ``expert`` participate in batch sharding — so DDP gradient
    reduction runs over all four. Only axes the mesh actually has are
    returned, so hand-built meshes (e.g. ``Mesh(devices, ('data',))``)
    work too.
    """
    return tuple(
        a for a in ("dcn", "data", "fsdp", "expert") if a in mesh.shape
    )


def dcn_size(mesh) -> int:
    """Slice count of the mesh's ``dcn`` axis (1 on flat meshes)."""
    return int(mesh.shape.get("dcn", 1))


def slice_block_size(mesh) -> int:
    """Devices per slice in the mesh's flattened device order.

    ``dcn`` is the OUTERMOST axis, so flattened device ids group into
    contiguous per-slice blocks of this size — the id arithmetic the
    HLO replica-group ici/dcn attribution keys on
    (obs/xprof.hlo_axis_traffic).
    """
    return mesh.devices.size // dcn_size(mesh)
