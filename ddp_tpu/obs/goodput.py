"""FLOPs estimators, MFU arithmetic, and restart-aware goodput.

MFU (model FLOPs utilization) is the production TPU efficiency metric
("Scalable Training of Language Models using JAX pjit and TPUv4"
reports it as the headline number): analytic model FLOPs actually
trained per second, divided by the chip's peak. It needs two inputs
this module owns — a per-model **train-FLOPs-per-example estimator**
(matmul/conv arithmetic only, the community convention; fwd ≈ the
model's matmuls, train ≈ 3× fwd for fwd+bwd) and a **per-chip peak**.

Peaks come from public spec sheets for TPU generations. A CPU has no
peak, so off-TPU MFU is absent (never a number under a device
metric's name); a TPU ``device_kind`` the table does not list is an
error, not a default.

Goodput is the restart-aware companion: productive training seconds
divided by wall seconds since the FIRST launch, persisted in a
``goodput.json`` sidecar next to the checkpoints so preemptions and
auto-resumes (train/trainer.py) accumulate instead of resetting —
a run that crash-loops shows its true cost.
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Callable, Optional

# ---- per-chip peak ---------------------------------------------------

# bf16 peak FLOP/s per chip by device kind (public spec sheets): the
# denominator of the trainer's MFU field.
TPU_BF16_PEAK = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops_per_chip(device=None) -> Optional[float]:
    """Per-chip bf16 peak for MFU. ``device`` defaults to
    jax.devices()[0].

    A listed TPU kind → its spec-sheet peak (the compute dtype every
    perf config here runs). Any other platform → None: it has no peak
    here, and ``mfu`` then reports None. A TPU kind the table does not
    list raises — add the kind with its source rather than print a
    utilization against a made-up peak.
    """
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    kind = device.device_kind
    for prefix, peak in TPU_BF16_PEAK.items():
        if kind.startswith(prefix):
            return peak
    raise ValueError(
        f"no bf16 peak for TPU device_kind {kind!r}: add it to "
        "ddp_tpu.obs.goodput.TPU_BF16_PEAK with its spec-sheet source "
        f"(known: {sorted(TPU_BF16_PEAK)})"
    )


def mfu(
    examples_per_sec: float,
    flops_per_example: Optional[float],
    peak: Optional[float],
) -> Optional[float]:
    """Fraction of peak, or None when either input is unknown."""
    if not flops_per_example or not peak or peak <= 0:
        return None
    if not math.isfinite(examples_per_sec) or examples_per_sec < 0:
        return None
    return examples_per_sec * flops_per_example / peak


# ---- analytic FLOPs estimators ---------------------------------------
#
# All return TRAIN flops per example (3× forward: fwd + ~2× bwd), with
# forward = the matmul/conv terms only. Elementwise/norm/softmax work
# is excluded by convention — MFU compares against the MXU peak, which
# only the contractions can use.
#
# Cross-checked against the compiler, not just golden-pinned: the
# xprof layer (obs/xprof.py) reads XLA's own op count off the compiled
# train step, and tests/test_xprof.py pins measured/analytic within a
# per-family tolerance band (near 1 for the conv nets, above 1 for
# tiny transformers where the excluded elementwise work is a visible
# share). An estimator edit that drifts from the real program now
# fails there, not in a quiet MFU skew.


def conv_flops(h_out: int, w_out: int, k: int, c_in: int, c_out: int) -> float:
    return 2.0 * h_out * w_out * k * k * c_in * c_out


def cnn_train_flops(
    image_shape=(28, 28, 1),
    num_classes: int = 10,
    *,
    features=(32, 64),
    depth=None,  # registry-uniform signature; SimpleCNN has no depth knob
) -> float:
    """models/cnn.py SimpleCNN: two SAME 3×3 convs + flatten + fc."""
    h, w, c = image_shape
    f0, f1 = features
    fwd = (
        conv_flops(h, w, 3, c, f0)
        + conv_flops(h, w, 3, f0, f1)
        + 2.0 * (h * w * f1) * num_classes
    )
    return 3.0 * fwd


def resnet_train_flops(
    image_shape=(32, 32, 3),
    num_classes: int = 10,
    *,
    stage_sizes=(2, 2, 2, 2),
    bottleneck: bool = False,
    width: int = 64,
    cifar_stem: bool = True,
    depth=None,  # structure comes from stage_sizes here
) -> float:
    """models/resnet.py: walks the exact stage/stride structure."""
    h, _, c = image_shape
    fwd = 0.0
    if cifar_stem:
        fwd += conv_flops(h, h, 3, c, width)
    else:
        h = -(-h // 2)
        fwd += conv_flops(h, h, 7, c, width)
        h = -(-h // 2)  # 3×3/2 max pool, SAME
    c = width
    for stage, num_blocks in enumerate(stage_sizes):
        f = width * 2**stage
        out = f * 4 if bottleneck else f
        for block_idx in range(num_blocks):
            strides = 2 if stage > 0 and block_idx == 0 else 1
            h_out = -(-h // strides)
            if bottleneck:
                fwd += conv_flops(h, h, 1, c, f)  # 1×1 reduce (pre-stride)
                fwd += conv_flops(h_out, h_out, 3, f, f)
                fwd += conv_flops(h_out, h_out, 1, f, out)
            else:
                fwd += conv_flops(h_out, h_out, 3, c, f)
                fwd += conv_flops(h_out, h_out, 3, f, f)
            if c != out or strides != 1:
                fwd += conv_flops(h_out, h_out, 1, c, out)  # downsample
            h, c = h_out, out
    fwd += 2.0 * c * num_classes
    return 3.0 * fwd


def transformer_block_fwd_flops_per_token(
    d: int,
    total_len: int,
    *,
    num_heads: int = 1,
    num_kv_heads: int = 0,
    mlp_ratio: int = 4,
    causal: bool = False,
    moe: bool = False,
    num_experts: int = 0,
    top_k: int = 2,
) -> float:
    """One pre-LN encoder/decoder block, per token.

    qkv + output projections, the two attention matmuls (QK^T and
    attn·V — halved for causal masking), and the MLP (top_k experts'
    worth plus the router when ``moe``).
    """
    h_kv = num_kv_heads or num_heads
    qkv = 2.0 * d * d * (num_heads + 2 * h_kv) / num_heads
    proj = 2.0 * d * d
    keys = total_len / 2 if causal else total_len
    attn = 2.0 * 2.0 * keys * d
    if moe:
        mlp = top_k * 2.0 * 2.0 * mlp_ratio * d * d + 2.0 * d * num_experts
    else:
        mlp = 2.0 * 2.0 * mlp_ratio * d * d
    return qkv + proj + attn + mlp


def vit_train_flops(
    image_shape=(32, 32, 3),
    num_classes: int = 100,
    *,
    patch_size: int = 4,
    embed_dim: int = 192,
    depth: int = 12,
    num_heads: int = 3,
    mlp_ratio: int = 4,
    use_cls_token: bool = True,
    num_experts: int = 0,
    moe_every: int = 2,
    top_k: int = 2,
) -> float:
    """models/vit.py ViT (and moe.py MoEViT when num_experts > 0)."""
    from ddp_tpu.models.moe import is_moe_block

    h, _, c = image_shape
    T = (h // patch_size) ** 2 + (1 if use_cls_token else 0)
    d = embed_dim
    fwd = 2.0 * T * patch_size * patch_size * c * d  # patch embed
    for i in range(depth):
        is_moe = is_moe_block(i, num_experts, moe_every)
        fwd += T * transformer_block_fwd_flops_per_token(
            d, T, num_heads=num_heads, mlp_ratio=mlp_ratio,
            moe=is_moe, num_experts=num_experts, top_k=top_k,
        )
    fwd += 2.0 * d * num_classes  # head
    return 3.0 * fwd


def lm_train_flops_per_token(
    *,
    vocab_size: int,
    total_len: int,
    d_model: int,
    depth: int,
    num_heads: int = 4,
    num_kv_heads: int = 0,
    mlp_ratio: int = 4,
    num_experts: int = 0,
    moe_every: int = 2,
    moe_top_k: int = 2,
) -> float:
    """models/lm.py CausalLM: blocks + tied embedding head, per token.

    The PaLM-style 6N-per-token accounting expressed structurally so
    GQA (smaller kv projections) and MoE (top-k active experts +
    router) report their *active* FLOPs, not total parameters.
    """
    from ddp_tpu.models.moe import is_moe_block

    fwd = 0.0
    for i in range(depth):
        fwd += transformer_block_fwd_flops_per_token(
            d_model, total_len,
            num_heads=num_heads, num_kv_heads=num_kv_heads,
            mlp_ratio=mlp_ratio, causal=True,
            moe=is_moe_block(i, num_experts, moe_every),
            num_experts=num_experts, top_k=moe_top_k,
        )
    fwd += 2.0 * d_model * vocab_size  # tied logits matmul
    return 3.0 * fwd


def lm_train_flops_per_sequence(spec) -> float:
    """Per-SEQUENCE train FLOPs for an LMSpec-shaped object (the
    trainer's examples are sequences; throughput is sequences/sec)."""
    return spec.total_len * lm_train_flops_per_token(
        vocab_size=spec.vocab_size,
        total_len=spec.total_len,
        d_model=spec.d_model,
        depth=spec.depth,
        num_heads=spec.num_heads,
        num_kv_heads=getattr(spec, "num_kv_heads", 0),
        mlp_ratio=getattr(spec, "mlp_ratio", 4),
        num_experts=getattr(spec, "num_experts", 0),
        moe_every=getattr(spec, "moe_every", 2),
        moe_top_k=getattr(spec, "moe_top_k", 2),
    )


def seq_classifier_train_flops(spec) -> float:
    """models/seq_transformer.py long-context classifier, per sequence."""
    T, d = spec.total_len, spec.d_model
    fwd = 2.0 * T * spec.d_in * d  # input projection
    fwd += T * spec.depth * transformer_block_fwd_flops_per_token(
        d, T, num_heads=spec.num_heads,
    )
    fwd += 2.0 * d * spec.num_classes
    return 3.0 * fwd


# ---- registry (keyed by models/__init__ registry names) --------------

FLOPS_ESTIMATORS: dict[str, Callable[..., float]] = {}


def register_flops(name: str):
    def deco(fn):
        FLOPS_ESTIMATORS[name] = fn
        return fn

    return deco


register_flops("simple_cnn")(cnn_train_flops)
register_flops("resnet18")(
    lambda image_shape, num_classes, depth=None: resnet_train_flops(
        image_shape, num_classes, stage_sizes=(2, 2, 2, 2),
    )
)
register_flops("resnet34")(
    lambda image_shape, num_classes, depth=None: resnet_train_flops(
        image_shape, num_classes, stage_sizes=(3, 4, 6, 3),
        cifar_stem=False,
    )
)
register_flops("resnet50")(
    lambda image_shape, num_classes, depth=None: resnet_train_flops(
        image_shape, num_classes, stage_sizes=(3, 4, 6, 3),
        bottleneck=True, cifar_stem=False,
    )
)
register_flops("vit_tiny")(
    lambda image_shape, num_classes, depth=None: vit_train_flops(
        image_shape, num_classes, patch_size=4, embed_dim=192,
        depth=depth or 12, num_heads=3,
    )
)
register_flops("vit_micro")(
    lambda image_shape, num_classes, depth=None: vit_train_flops(
        image_shape, num_classes, patch_size=7, embed_dim=32,
        depth=depth or 2, num_heads=4,
    )
)
register_flops("vit_moe_tiny")(
    lambda image_shape, num_classes, depth=None: vit_train_flops(
        image_shape, num_classes, patch_size=4, embed_dim=192,
        depth=depth or 12, num_heads=3, num_experts=8,
    )
)
register_flops("vit_moe_micro")(
    lambda image_shape, num_classes, depth=None: vit_train_flops(
        image_shape, num_classes, patch_size=7, embed_dim=32,
        depth=depth or 2, num_heads=4, num_experts=4,
    )
)


def train_flops_per_example(
    model: str,
    *,
    image_shape=None,
    num_classes: int = 10,
    depth: Optional[int] = None,
) -> Optional[float]:
    """Registry-model estimate, or None for unknown models.

    None (not 0) on unknown: a missing estimator must make MFU absent,
    never silently 0 — an unmeasured run and a broken run are
    different facts.
    """
    fn = FLOPS_ESTIMATORS.get(model)
    if fn is None:
        return None
    return fn(tuple(image_shape or (28, 28, 1)), num_classes, depth=depth)


# ---- restart-aware goodput -------------------------------------------


class GoodputAccountant:
    """Productive seconds ÷ wall seconds since FIRST launch.

    State lives in a JSON sidecar (next to the checkpoints, like the
    tokenizer and lm_spec sidecars) so auto-resume accumulates across
    process restarts::

        {"first_launch_unix": ..., "productive_s": ..., "restarts": N,
         "world_size": W, "last_flush_unix": ...,
         "restart_downtime_s": ..., "resize_downtime_s": ...,
         "resizes": M}

    ``start_run()`` loads-or-initializes (counting a restart when a
    previous run's sidecar exists), ``add_productive()`` accrues step/
    epoch seconds, ``flush()`` writes atomically — called per epoch so
    a kill between epochs loses at most one epoch of accounting.
    ``enabled=False`` (non-main ranks) makes everything a no-op.

    Restart vs RESIZE downtime: each relaunch's downtime — the wall
    time between the dead generation's last flush and this
    generation's ``start_run()``, i.e. the unproductive tail of the
    killed epoch plus reap/backoff/re-init — is attributed by whether
    the world CHANGED SIZE across the boundary. Same size: ordinary
    restart downtime (a crash loop). Different size: resize downtime
    (an elastic scale-down/up, runtime/launch.py ``elastic=True``).
    The split is what lets capacity planning separate "our jobs crash"
    from "our fleet gets preempted and reshapes" — accounted downtime,
    not a mystery gap. Callers pass the live ``world_size`` to
    ``start_run``; ``prev_world`` then holds the size the previous
    generation recorded (None on first launch / legacy sidecars).
    """

    def __init__(
        self,
        sidecar_path: Optional[str],
        *,
        enabled: bool = True,
        clock: Callable[[], float] = time.time,
    ):
        self.path = sidecar_path
        self.enabled = bool(enabled and sidecar_path)
        self.clock = clock
        self.first_launch: float | None = None
        self.productive_s = 0.0
        self.restarts = 0
        self.world_size: int | None = None
        self.prev_world: int | None = None
        self.restart_downtime_s = 0.0
        self.resize_downtime_s = 0.0
        self.resizes = 0

    def start_run(self, world_size: int | None = None) -> None:
        self.world_size = world_size
        if not self.enabled:
            return
        state = None
        try:
            with open(self.path) as f:
                state = json.load(f)
        except (OSError, json.JSONDecodeError):
            state = None
        if isinstance(state, dict) and "first_launch_unix" in state:
            self.first_launch = float(state["first_launch_unix"])
            self.productive_s = float(state.get("productive_s", 0.0))
            self.restarts = int(state.get("restarts", 0)) + 1
            self.restart_downtime_s = float(
                state.get("restart_downtime_s", 0.0)
            )
            self.resize_downtime_s = float(
                state.get("resize_downtime_s", 0.0)
            )
            self.resizes = int(state.get("resizes", 0))
            prev = state.get("world_size")
            self.prev_world = int(prev) if prev else None
            # Downtime of the boundary just crossed: last durable
            # flush of the dead generation → now. Legacy sidecars
            # without the flush stamp contribute 0 (unknowable, not
            # invented).
            down = max(
                0.0, self.clock() - float(state.get("last_flush_unix", self.clock()))
            )
            if (
                world_size is not None
                and self.prev_world is not None
                and world_size != self.prev_world
            ):
                self.resizes += 1
                self.resize_downtime_s += down
            else:
                self.restart_downtime_s += down
        else:
            self.first_launch = self.clock()
            self.productive_s = 0.0
            self.restarts = 0
            self.prev_world = None

    def add_productive(self, seconds: float) -> None:
        if self.enabled and math.isfinite(seconds) and seconds > 0:
            self.productive_s += seconds

    def snapshot(self) -> dict:
        if not self.enabled or self.first_launch is None:
            return {}
        wall = max(1e-9, self.clock() - self.first_launch)
        out = {
            "goodput": round(self.productive_s / wall, 6),
            "productive_s": round(self.productive_s, 3),
            "wall_s": round(wall, 3),
            "restarts": self.restarts,
            "first_launch_unix": round(self.first_launch, 3),
        }
        if self.restarts or self.resizes:
            out["restart_downtime_s"] = round(self.restart_downtime_s, 3)
            out["resize_downtime_s"] = round(self.resize_downtime_s, 3)
            out["resizes"] = self.resizes
        return out

    def flush(self) -> None:
        if not self.enabled or self.first_launch is None:
            return
        os.makedirs(os.path.dirname(os.path.abspath(self.path)), exist_ok=True)
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(
                {
                    "first_launch_unix": self.first_launch,
                    "productive_s": self.productive_s,
                    "restarts": self.restarts,
                    "world_size": self.world_size,
                    "last_flush_unix": self.clock(),
                    "restart_downtime_s": self.restart_downtime_s,
                    "resize_downtime_s": self.resize_downtime_s,
                    "resizes": self.resizes,
                },
                f,
            )
        os.replace(tmp, self.path)
