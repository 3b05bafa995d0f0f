#!/usr/bin/env python
"""Headline benchmark: MNIST DDP training throughput, images/sec/chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N}

Baseline is the driver's north-star target of 50,000 images/sec/chip on
TPU (BASELINE.json) — the reference itself publishes no numbers
(/root/reference/README.md has only a quickstart; see BASELINE.md).

Measures the compiled-epoch fast path (ddp_tpu/train/fast.py): dataset
device-resident as uint8, per-epoch shuffle on device, ``lax.scan`` over
per-batch DDP steps — one dispatch per epoch. This is the framework's
answer to the reference's hot loop (train_ddp.py:195-202), which pays a
Python→C++ crossing per op and a collective sync per batch.
"""

from __future__ import annotations

import json
import os
import time

BASELINE_IMAGES_PER_SEC_PER_CHIP = 50_000.0


def _bench_trace_path(name: str) -> str:
    """Where a bench's span trace lands (ddp_tpu.obs tracer export).

    Default ./bench_traces beside the BENCH_*.json records;
    DDP_TPU_BENCH_TRACE_DIR relocates (e.g. CI artifact dirs).
    """
    d = os.environ.get("DDP_TPU_BENCH_TRACE_DIR", "./bench_traces")
    return os.path.abspath(os.path.join(d, f"{name}.trace.json"))


def _lint_clean() -> bool:
    """Self-lint verdict stamped on headline records (never raises —
    a linter crash reads as not-clean, loudly, not as a dead bench)."""
    try:
        from ddp_tpu.analysis import self_lint_clean

        return self_lint_clean()
    except Exception:
        return False


def _env_fields() -> dict:
    """Capture provenance every record carries: platform, backend, and
    an explicit ``cpu_fallback`` flag.

    ``python bench.py`` refuses to start off-TPU, but the ``run_*``
    entries stay callable on their own (the examples run several on
    the CPU): the flag makes "never compare an on-chip number against
    a CPU one" greppable in one field, in every entry.
    """
    import jax

    platform = jax.devices()[0].platform
    return {
        "platform": platform,
        "backend": jax.default_backend(),
        "cpu_fallback": platform == "cpu",
    }


def _assert_provenance(fields: dict) -> None:
    """Pin a record's published provenance to the LIVE backend.

    ``_env_fields`` output asserted against a fresh read of jax at
    publish time: a stale dict captured before a backend flip, copied
    from another record, or mutated downstream fails loudly here
    instead of poisoning the perf trajectory (a CPU record that claims
    otherwise is worse than no record).
    """
    import jax

    live = jax.devices()[0].platform
    assert (
        fields["platform"] == live
        and fields["backend"] == jax.default_backend()
        and fields["cpu_fallback"] == (live == "cpu")
    ), (fields, live)


def run_bench(
    *,
    global_batch_size: int = 16384,
    warmup_epochs: int = 2,
    timed_epochs: int = 10,
) -> dict:
    # Defaults from a sweep on the v4 chip (2026-07): 16384 beat 4096
    # (419k) and 32768 (430k) at 462k images/sec/chip; 10 timed epochs
    # amortize dispatch/timer noise that dominates sub-second windows.
    # Profiled (xprof op_profile, 2026-07): >50% of device time is the
    # conv2 fwd/grad fusions at ~7% MXU util — the 16384×28×28×32
    # bf16 activations (~0.8 GB/tensor) make the step HBM-bandwidth
    # bound, so batch size and kernel tweaks move it little; the
    # remaining headroom would need an architecture change, not
    # scheduling.
    import jax
    import jax.numpy as jnp
    import optax

    from ddp_tpu.data import mnist
    from ddp_tpu.models import get_model
    from ddp_tpu.parallel.ddp import create_train_state, replicate_state
    from ddp_tpu.runtime.mesh import MeshSpec, make_mesh
    from ddp_tpu.train.fast import device_put_dataset, make_epoch_runner

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the chip and JAX found {devices[0]!r} "
            "— no TPU device. It does not fall back to the CPU: a CPU "
            "number is not a smaller chip number"
        )
    mesh = make_mesh(MeshSpec(data=len(devices)), devices=devices)

    train = mnist.load("./data", "train", allow_synthetic=True)
    n = (train.images.shape[0] // global_batch_size) * global_batch_size
    images, labels = device_put_dataset(
        train.images[:n], train.labels[:n], mesh
    )

    model = get_model("simple_cnn")
    tx = optax.sgd(0.01)
    state = replicate_state(
        create_train_state(model, tx, jnp.zeros((1, 28, 28, 1)), seed=0), mesh
    )
    # Compiled-program introspection for the headline (obs/xprof.py):
    # the fast path is an epoch-runner closure, so its ledger entry is
    # observe-only (first-dispatch wall time, flagged ``fallback``);
    # the record carries compile_time_s and the HBM high-water.
    from ddp_tpu.obs.xprof import DeviceMemorySampler, Xprof

    xprof = Xprof(enabled=True)
    hbm = DeviceMemorySampler(enabled=True)
    runner = xprof.instrument(
        make_epoch_runner(
            model,
            tx,
            mesh,
            images,
            labels,
            global_batch_size,
            compute_dtype=jnp.bfloat16,
            seed=0,
        ),
        "bench_epoch",
    )
    images_per_epoch = runner.steps_per_epoch * global_batch_size

    from ddp_tpu.obs.goodput import cnn_train_flops, peak_flops_per_chip
    from ddp_tpu.obs.tracer import Tracer

    tracer = Tracer(enabled=True, ring_events=4096)
    for e in range(warmup_epochs):  # compile + stabilize clocks
        with tracer.span("bench.warmup_epoch", {"epoch": e}):
            state, metrics = runner(state, e)
            jax.block_until_ready(metrics.loss)
    hbm.sample()  # post-compile steady state

    t0 = time.perf_counter()
    for e in range(warmup_epochs, warmup_epochs + timed_epochs):
        with tracer.span("bench.epoch", {"epoch": e}):
            state, metrics = runner(state, e)
    jax.block_until_ready(metrics.loss)
    seconds = time.perf_counter() - t0
    hbm.sample()

    total_images = images_per_epoch * timed_epochs
    per_chip = total_images / seconds / len(devices)
    flops_per_image = cnn_train_flops((28, 28, 1), 10)
    mfu = per_chip * flops_per_image / peak_flops_per_chip(devices[0])
    try:
        trace = tracer.export(_bench_trace_path("mnist_ddp"))
    except OSError:
        trace = None  # read-only checkout: the record survives
    env = _env_fields()
    _assert_provenance(env)
    return {
        "metric": "mnist_ddp_train_throughput",
        "value": round(per_chip, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / BASELINE_IMAGES_PER_SEC_PER_CHIP, 3),
        **env,
        "num_chips": len(devices),
        "global_batch_size": global_batch_size,
        "timed_epochs": timed_epochs,
        "final_loss": round(float(metrics.loss[-1]), 4),
        "seconds": round(seconds, 3),
        "mfu": round(mfu, 6),
        "trace": trace,
        # Compiled-program ledger (obs/xprof.py): what this number
        # paid in XLA builds, and the device-memory high-water of the
        # measured loop. compile_measured says what compile_time_s IS:
        # "aot" = real lower().compile() seconds; "first_call" = the
        # observe-only fallback's whole first dispatch (compile + one
        # epoch of steps — the epoch-runner closure can't lower),
        # which must never be compared against an aot number.
        "compile_time_s": round(xprof.total_compile_s, 3),
        "compile_measured": (
            "first_call"
            if any(r.get("fallback") for r in xprof.ledger_records())
            else "aot"
        ),
        "compiled_programs": xprof.program_count,
        "hbm_high_water_bytes": hbm.high_water_bytes,
        # Self-lint status of the measured tree (scripts/lint.py
        # --self, ddp_tpu.analysis): False means this number was
        # captured on a tree with unsuppressed distributed-JAX hazard
        # findings — a lint regression shows up in the perf-trajectory
        # sidecars next to the throughput it might be corrupting.
        "lint_clean": _lint_clean(),
    }


# --- MXU-bound side benchmarks (VERDICT.md round-1 "do this" #2) -----
#
# The headline MNIST number is HBM-bound (see run_bench notes); these
# measure the models where the TPU-first design actually pays — the
# attention path in bf16 with the Pallas flash kernel — and report an
# MFU estimate. Results go to BENCH_EXTRA.json + stderr; stdout stays
# the single headline JSON line (the driver contract).

# bf16 peak FLOP/s per chip by device kind: one table, owned by the
# observability subsystem (ddp_tpu/obs/goodput.py) so bench and the
# trainer's MFU accounting cannot drift.


def _mfu(rate: float, flops_per_unit: float, device) -> float | None:
    """``rate`` units/s × FLOPs per unit over the chip's bf16 peak;
    None off-TPU, where there is no peak (obs/goodput.py owns the
    rule — an unlisted TPU kind raises)."""
    from ddp_tpu.obs.goodput import mfu, peak_flops_per_chip

    m = mfu(rate, flops_per_unit, peak_flops_per_chip(device))
    return None if m is None else round(m, 6)


def _timed_device_loop(run, state, *, repeats: int = 3):
    """Time ``run(state, seed)`` — one dispatch scanning ``nsteps``
    training steps on device — syncing on the returned scalar.

    Best of ``repeats`` timed dispatches: the device work is
    deterministic per seed, so the spread between repeats is host
    scheduling noise, and the minimum is the measurement closest to
    the device's own rate.
    """
    import time

    loss = float(run(state, 1))  # compile + warm (full sync via float)
    seconds = float("inf")
    for r in range(repeats):
        t0 = time.perf_counter()
        loss = float(run(state, 2 + r))
        seconds = min(seconds, time.perf_counter() - t0)
    return loss, seconds


def run_vit_bench(
    *, batch: int = 256, nsteps: int = 30, use_cls_token: bool = True
) -> dict:
    """ViT-Tiny bf16 training throughput (images/sec/chip + MFU est).

    CIFAR-100-shaped synthetic data generated on device; one jitted
    dispatch scans ``nsteps`` full train steps (fwd+bwd+SGD), so
    per-call dispatch cost cannot pollute the timing. The
    attention hot op is the Pallas flash kernel (ops/flash.py) via the
    model-zoo default.

    ``use_cls_token=False`` is the round-4 layout-tax experiment
    (round-3 verdict weak #5): T drops from 65 to 64 — a whole tile
    multiple — by mean-pooling instead of a cls token, attacking the
    measured ~30% of step time in 'data formatting'/'copy-done' that
    the T=65 padding forces. Published as the ``vit_t64`` entry beside
    the T=65 one.
    """
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax

    from ddp_tpu.models import get_model

    device = jax.devices()[0]
    if use_cls_token:
        model = get_model("vit_tiny", num_classes=100)
    else:
        from ddp_tpu.models.vit import ViT

        model = ViT(
            num_classes=100, patch_size=4, embed_dim=192, depth=12,
            num_heads=3, use_cls_token=False,
        )
    tx = optax.sgd(0.01, momentum=0.9)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 32, 32, 3), jnp.float32)
    )["params"]
    opt_state = tx.init(params)

    def step(carry, key):
        params, opt_state = carry
        # One key per consumer (self-lint DDP005): sharing `key`
        # between normal() and randint() draws labels CORRELATED with
        # the images — a synthetic batch the model can partially read
        # the answer from.
        k_img, k_lbl = jax.random.split(key)
        images = jax.random.normal(k_img, (batch, 32, 32, 3), jnp.bfloat16)
        labels = jax.random.randint(k_lbl, (batch,), 0, 100)

        def loss_fn(p):
            pb = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
            logits = model.apply({"params": pb}, images.astype(jnp.bfloat16))
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state), loss

    @jax.jit
    def run(state, seed):
        keys = jax.random.split(jax.random.key(seed), nsteps)
        (params, opt_state), losses = lax.scan(step, state, keys)
        return losses[-1]

    loss, seconds = _timed_device_loop(run, (params, opt_state))
    images_per_sec = batch * nsteps / seconds

    # Analytic train FLOPs/image (fwd ≈ blocks' matmuls + attention;
    # backward ≈ 2× forward). T = 64 patches (8×8) + optional cls.
    d, depth = 192, 12
    T = (32 // 4) ** 2 + (1 if use_cls_token else 0)
    fwd = depth * (24 * T * d * d + 4 * T * T * d)
    train_flops_per_image = 3 * fwd
    mfu = _mfu(images_per_sec, train_flops_per_image, device)
    return {
        "metric": "vit_tiny_bf16_train_throughput",
        "value": round(images_per_sec, 1),
        "unit": "images/sec/chip",
        **_env_fields(),
        "tokens": T,
        "use_cls_token": use_cls_token,
        "batch": batch,
        "nsteps": nsteps,
        "final_loss": round(loss, 4),
        "train_flops_per_image": train_flops_per_image,
        "estimated_mfu": round(mfu, 4) if mfu is not None else None,
        "mfu": mfu,
        "device_kind": getattr(device, "device_kind", "unknown"),
    }


def run_lm_bench(
    *, batch: int = 8, seq_len: int = 2048, nsteps: int = 10
) -> dict:
    """Causal-LM training throughput (tokens/sec/chip + MFU est).

    A real MXU workload: d_model 1024, depth 8, heads 8 (head_dim 128
    — wider contractions fill the MXU; measured ~0.48-0.51 estimated MFU
    across runs on the v5e at this config vs 0.39 at d_model 512), T 2048, causal
    flash attention (Pallas) by model-zoo default, bf16 compute.
    Driven through the SHIPPED compiled-epoch path the trainer's
    ``--fast_epoch`` uses (train/fast.py make_lm_epoch_runner — the
    round-3 ask #9 lift): a device-resident token dataset, per-epoch
    on-device shuffle, one dispatch per epoch of ``nsteps`` steps of
    the same raw make_lm_train_step. 1×1 data×seq mesh.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ddp_tpu.models.lm import LMSpec, create_lm_train_state
    from ddp_tpu.runtime.mesh import MeshSpec, make_mesh
    from ddp_tpu.train.fast import (
        device_put_replicated,
        make_lm_epoch_runner,
    )

    device = jax.devices()[0]
    vocab, d, depth, heads = 8192, 1024, 8, 8
    mesh = make_mesh(MeshSpec(data=1, seq=1), devices=[device])
    spec = LMSpec(
        vocab_size=vocab, total_len=seq_len, d_model=d, depth=depth,
        num_heads=heads,
    )
    tx = optax.adam(3e-4)
    state = create_lm_train_state(spec, tx, mesh, seed=0)
    rng = np.random.default_rng(0)
    tokens = device_put_replicated(
        rng.integers(0, vocab, (batch * nsteps, seq_len), dtype=np.int32),
        mesh,
    )
    runner = make_lm_epoch_runner(
        spec, tx, mesh, tokens, batch,
        compute_dtype=jnp.bfloat16, donate=False,
    )
    assert runner.steps_per_epoch == nsteps

    def run(state, epoch):
        _, metrics = runner(state, epoch)
        return metrics.loss[-1]

    loss, seconds = _timed_device_loop(run, state)
    tokens_per_sec = batch * seq_len * nsteps / seconds

    # PaLM-style estimate: 6·N per token (fwd+bwd matmuls) + causal
    # attention 3.5 × 2 matmuls × T/2 keys × d.
    n_params = depth * 12 * d * d + vocab * d  # tied embedding
    attn = 3.5 * 2 * 2 * (seq_len / 2) * d * depth
    train_flops_per_token = 6 * n_params + attn
    mfu = _mfu(tokens_per_sec, train_flops_per_token, device)
    return {
        "metric": "causal_lm_train_throughput",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        **_env_fields(),
        "batch": batch,
        "seq_len": seq_len,
        "nsteps": nsteps,
        "d_model": d,
        "depth": depth,
        "final_loss": round(loss, 4),
        "train_flops_per_token": round(train_flops_per_token),
        "estimated_mfu": round(mfu, 4) if mfu is not None else None,
        "mfu": mfu,
        "device_kind": getattr(device, "device_kind", "unknown"),
    }


def run_lm_long_bench(*, batch: int = 2, seq_len: int = 8192) -> dict:
    """Long-context causal-LM training at T=8192 (flash attention).

    Same model family and step path as run_lm_bench but in the regime
    the flash kernel exists for: O(T) attention memory where dense
    attention would materialize [B, H, T, T] fp32 logits — 2·8·8192²
    = 4 GiB per materialization, several of which coexist across the
    fwd+bwd of 8 layers on a 16 GiB chip. Demonstrates long-context
    training on one chip is real, not extrapolated.
    """
    return {
        **run_lm_bench(batch=batch, seq_len=seq_len, nsteps=4),
        "metric": "causal_lm_long_context_train_throughput",
    }


def run_decode_bench(
    *, batch: int = 8, prompt_len: int = 128, new_tokens: int = 256,
    num_kv_heads: int = 0, num_experts: int = 0,
) -> dict:
    """Generation (serving-path) throughput: KV-cache greedy decode.

    Prefill runs (jitted) OUTSIDE the timed window; the measurement is
    one jitted ``lax.scan`` of decode steps (models/generate.py) on
    the bench LM config — the latency-bound regime (matmuls are
    [B, 1, d]-thin, HBM-bandwidth dominated), the complement of the
    training benches' throughput regime. ``num_kv_heads`` benches the
    GQA variant: the compact cache cuts per-step KV reads by the
    group factor (the ``decode_gqa`` entry records the effect).
    ``num_experts`` benches the round-5 MoE serving path (generate.py
    ``_moe_mlp``: dense E-way expert compute, top-k combine) — the
    ``decode_moe`` entry records routed-decode cost vs dense.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ddp_tpu.models.generate import decode_step, prefill
    from ddp_tpu.models.lm import LMSpec, init_lm

    device = jax.devices()[0]
    vocab, d, depth, heads = 8192, 1024, 8, 8
    spec = LMSpec(
        vocab_size=vocab, total_len=prompt_len + new_tokens, d_model=d,
        depth=depth, num_heads=heads, num_kv_heads=num_kv_heads,
        num_experts=num_experts,
    )
    params = init_lm(spec, seed=0)
    prompt = jnp.zeros((batch, prompt_len), jnp.int32)

    @jax.jit
    def do_prefill(p, pr):
        return prefill(spec, p, pr)

    @jax.jit
    def do_decode(p, logits, cache):
        def step(carry, _):
            logits, cache = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits, cache = decode_step(spec, p, cache, tok)
            return (logits, cache), tok

        (logits, _), toks_out = lax.scan(
            step, (logits, cache), None, length=new_tokens
        )
        return toks_out[-1, 0]

    logits, cache = do_prefill(params, prompt)
    _, best = _timed_device_loop(
        lambda s, _seed: do_decode(*s), (params, logits, cache)
    )
    toks = batch * new_tokens
    # Decode MFU: forward FLOPs/token (train estimate ÷ 3) over peak —
    # the latency-bound regime's honest MXU number (it is SUPPOSED to
    # be low; HBM bandwidth is the binding resource here).
    from ddp_tpu.obs.goodput import lm_train_flops_per_token

    fwd_per_token = lm_train_flops_per_token(
        vocab_size=vocab, total_len=spec.total_len, d_model=d,
        depth=depth, num_heads=heads, num_kv_heads=num_kv_heads,
        num_experts=num_experts,
    ) / 3.0
    return {
        "metric": "kv_cache_decode_throughput",
        "value": round(toks / best, 1),
        **_env_fields(),
        "mfu": _mfu(toks / best, fwd_per_token, device),
        "unit": "tokens/sec/chip",
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "d_model": d,
        "depth": depth,
        "num_heads": heads,
        "num_kv_heads": num_kv_heads or heads,
        "num_experts": num_experts,
        "per_token_ms": round(best / new_tokens * 1000, 3),
        "device_kind": getattr(device, "device_kind", "unknown"),
    }


def run_serve_bench(
    *,
    slots: int = 8,
    prefill_len: int = 128,
    new_tokens: int = 128,
    n_requests: int = 48,
    seed: int = 0,
) -> dict:
    """Serving-engine throughput under an open-loop arrival process.

    The ddp_tpu.serve regime: the continuous-batching engine
    (fixed-slot SlotCache, serve/engine.py) fed by Poisson arrivals
    whose rate is INDEPENDENT of service progress (open loop — the
    honest serving measurement; closed-loop clients hide queueing).
    Mixed prompt/output lengths exercise refill churn. The arrival
    rate is sized ~1.5× the engine's slot-seconds so the queue
    genuinely builds and drains — TTFT then includes queueing delay,
    which is the point: this entry reports what a user would see, not
    what a drained batch can do.

    Complements run_decode_bench: that measures the raw decode scan
    (one batch, no arrivals); this measures the whole data plane —
    admission, bucketed chunked prefill co-scheduled with the fused
    decode+sample step, device-resident token handoff, retirement —
    as sustained decode tokens/s, TTFT percentiles and per-step
    latency percentiles (p50/p99: chunk co-scheduling exists exactly
    to keep the p99 step near the p50 — a monolithic prefill would
    show up as a fat tail). The steady-state compile-count budget
    (buckets + decode) is asserted so shape-explosion regressions
    fail the bench fast. Serving metrics stream through
    utils/metrics.MetricsWriter the same way a real deployment's
    would (here: discarded; scripts/serve.py wires --metrics_file).
    """
    import time

    import jax
    import numpy as np

    from ddp_tpu.models.lm import LMSpec, init_lm
    from ddp_tpu.obs.goodput import lm_train_flops_per_token
    from ddp_tpu.obs.tracer import Tracer
    from ddp_tpu.serve.engine import ServeEngine

    device = jax.devices()[0]
    vocab, d, depth, heads = 8192, 1024, 8, 8
    if device.platform != "tpu":
        # Fallback shape: the engine logic is platform-free; keep the
        # CPU record minutes-cheap like the other benches' fallbacks.
        vocab, d, depth, heads = 512, 128, 2, 4
        slots, prefill_len = min(slots, 4), min(prefill_len, 32)
        new_tokens, n_requests = min(new_tokens, 32), min(n_requests, 12)
    spec = LMSpec(
        vocab_size=vocab, total_len=prefill_len + new_tokens,
        d_model=d, depth=depth, num_heads=heads,
    )
    params = init_lm(spec, seed=0)
    tracer = Tracer(enabled=True, ring_events=16384)
    # Request-level tracing + SLO evaluation over the bench run
    # (ISSUE 11): every request's admit→retire timeline reconstructs
    # from the exported trace (causally validated below), and the
    # record carries user-facing latency objectives evaluated over
    # the same traffic — recorded, never asserted (a CPU-fallback
    # capture legitimately breaches latency bounds sized for chips).
    from ddp_tpu.obs.slo import SLOEngine

    slo = SLOEngine(
        "ttft_p99<2s,tpot_p50<250ms,availability>0.999",
        min_eval_interval_s=0.0,
    )
    engine = ServeEngine(
        spec, params, slots=slots, prefill_len=prefill_len,
        max_queue=max(16, n_requests), tracer=tracer,
        reqtrace=True, trace_seed=seed, slo=slo,
        # The coverage assert below needs every retired trace still
        # resident at emit time (the timed window runs untraced, so
        # nothing is emitted at retire) — size the retained ring to
        # the run, or a big-capture n_requests would evict the oldest
        # traces and fail the assert spuriously.
        reqtrace_keep=max(512, n_requests + slots),
    )

    rng = np.random.default_rng(seed)
    prompt_lens = rng.integers(8, prefill_len + 1, n_requests)
    budgets = rng.integers(new_tokens // 2, new_tokens + 1, n_requests)
    prompts = [
        rng.integers(0, vocab, int(n)).tolist() for n in prompt_lens
    ]

    # Warmup: eagerly compile the WHOLE bounded program set (one
    # first-chunk + one continuation-chunk program per bucket width,
    # plus the fused decode+sample step) outside the timed window —
    # and assert the compile-count BUDGET: a shape explosion
    # (per-length prefill, per-config decode) fails the bench before
    # it pollutes a published record.
    compile_counts = engine.warmup()
    compile_budget = engine.compile_budget()
    assert sum(compile_counts.values()) <= compile_budget, (
        f"engine program set {compile_counts} exceeds its budget of "
        f"2 x {len(engine.buckets)} chunk buckets + 1 decode program"
    )

    # Open-loop schedule: estimate per-step latency from a short
    # drive, then set the Poisson rate to ~1.5× service capacity.
    t0 = time.perf_counter()
    engine.submit(prompts[0], 8)
    engine.run()
    step_s = max(1e-4, (time.perf_counter() - t0) / 9)
    # Warmup/calibration TTFTs span XLA compilation — reset the
    # engine's latency summaries so the published percentiles reflect
    # the timed open-loop phase only ("what a user would see").
    from ddp_tpu.utils.metrics import StatSummary

    engine.ttft = StatSummary()
    engine.decode_rate = StatSummary()
    engine.step_latency = StatSummary()
    engine.queue_wait = StatSummary()
    engine.tpot = StatSummary()
    # The timed window runs UNTRACED: with tracing on, every dispatch
    # blocks until ready for span fidelity, which disables the
    # dispatch/retire overlap this bench exists to measure. The
    # exported trace keeps the warmup/calibration spans.
    tracer.enabled = False
    service_rate = slots / (step_s * float(np.mean(budgets)))
    arrival_rate = 1.5 * service_rate
    arrivals = np.cumsum(
        rng.exponential(1.0 / arrival_rate, n_requests)
    )

    t_start = time.perf_counter()
    rejected = 0
    max_queue_depth = 0
    timed_rids = []
    i = 0
    while i < n_requests or engine.pending:
        now = time.perf_counter() - t_start
        while i < n_requests and arrivals[i] <= now:
            adm = engine.submit(prompts[i], int(budgets[i]))
            if adm.accepted:
                timed_rids.append(adm.request.rid)
            else:
                rejected += 1
            i += 1
        max_queue_depth = max(max_queue_depth, engine.scheduler.depth)
        if engine.pending:
            engine.step()
        elif i < n_requests:
            time.sleep(min(0.005, max(0.0, arrivals[i] - now)))
    wall = time.perf_counter() - t_start
    tracer.enabled = True
    # The engine records its own per-step latency (reset above so the
    # summary covers exactly the timed open-loop window).
    step_lat = engine.step_latency

    total_tokens = sum(
        len(engine.result(r).tokens)
        for r in timed_rids
        if engine.result(r) is not None
    )
    assert engine.compile_counts() == compile_counts, (
        "serve bench recompiled after warmup — static-shape invariant "
        f"broken: {compile_counts} -> {engine.compile_counts()}"
    )
    # The /metricsz exposition must stay scrapeable under a real
    # traffic mix: render the live engine counters and run the lint
    # (obs/promtext.py) so a renderer regression fails the bench too,
    # not just the smoke tier.
    from ddp_tpu.obs.promtext import render_serve, validate_promtext

    promtext_samples = validate_promtext(
        render_serve(engine.stats(), up=True)
    )
    fwd_per_token = lm_train_flops_per_token(
        vocab_size=vocab, total_len=spec.total_len, d_model=d,
        depth=depth, num_heads=heads,
    ) / 3.0
    # Per-request timeline acceptance (ISSUE 11): the timed window ran
    # with measuring mode off (overlap preserved), so emit the retired
    # request spans retroactively, then require that EVERY completion
    # reconstructs to a complete, causally-ordered admit→retire
    # timeline from the trace — a broken lifecycle event fails the
    # bench, not just a test.
    from ddp_tpu.obs.reqtrace import (
        reconstruct_requests,
        validate_request_timeline,
    )

    engine.emit_request_spans()
    timelines = reconstruct_requests(
        tracer.trace_document()["traceEvents"]
    )
    for tid, timeline in timelines.items():
        validate_request_timeline(timeline)  # raises naming the hole
    assert len(timelines) == len(engine._completed), (
        f"request-trace coverage broken: {len(timelines)} timelines "
        f"for {len(engine._completed)} completions"
    )
    try:
        trace = tracer.export(_bench_trace_path("serve_decode"))
    except OSError:
        trace = None

    # ---- decode-path variants (ISSUE 10) ----------------------------
    # Same model, same traffic, four engine configs: the PR-3 baseline
    # (jnp reference attention, fp32 cache), flash-decode (the engine's
    # auto selection: Pallas kernel on TPU, the bit-identical reference
    # off-TPU — forcing the interpreter here would measure the
    # interpreter, not the kernel), +speculative (γ=4 greedy drafts
    # from a truncated-depth draft sharing the target's weights — the
    # zero-training draft; --draft_checkpoint_dir wires a real one),
    # and +int8 KV (quantize-on-write cache). Each sub-record carries
    # steady-state step-latency p50/p99, tokens/s, acceptance, cache
    # bytes/slot, and the PR-9 provenance fields so a CPU-fallback
    # capture can never be compared against an on-chip one.
    from ddp_tpu.utils.metrics import StatSummary as _SS

    def _variant(name: str, **ekw) -> dict:
        v_eng = ServeEngine(
            spec, params, slots=slots, prefill_len=prefill_len,
            max_queue=4 * slots, **ekw,
        )
        counts = v_eng.warmup()
        assert sum(counts.values()) <= v_eng.compile_budget(), (
            f"variant {name} program set {counts} exceeds its budget "
            f"{v_eng.compile_budget()}"
        )
        v_rng = np.random.default_rng(seed + 1)  # same traffic per variant
        for _ in range(2 * slots):
            plen = int(v_rng.integers(8, max(9, prefill_len // 2 + 1)))
            v_eng.submit(
                v_rng.integers(0, vocab, plen).tolist(), new_tokens
            )
        v_eng.step()  # settle admission/prefill before timing
        v_eng.step_latency = _SS()
        v0 = time.perf_counter()
        while v_eng.pending:
            v_eng.step()
        v_wall = time.perf_counter() - v0
        v_tokens = sum(
            len(c.tokens) for c in v_eng._completed.values()
        )
        lat = v_eng.step_latency
        assert v_eng.compile_counts() == counts, (
            f"variant {name} recompiled after warmup"
        )
        return {
            "attn_impl": v_eng.decode_attn,
            "kv_dtype": v_eng.kv_dtype,
            "spec_tokens": v_eng.spec_tokens,
            "step_latency_s": {
                "count": lat.count,
                "p50": round(lat.percentile(50), 6) if lat.count else None,
                "p99": round(lat.percentile(99), 6) if lat.count else None,
            },
            "tokens_per_s": round(v_tokens / v_wall, 1),
            "total_tokens": v_tokens,
            "acceptance_rate": v_eng.spec_acceptance_rate(),
            "cache_bytes_per_slot": v_eng.cache_bytes_per_slot(),
            "compile_programs": sum(counts.values()),
            "compile_budget": v_eng.compile_budget(),
            # Paged-KV pool/prefix gauges (PR 12) — absent on the
            # fixed-lane variants, same gate as /metricsz.
            **(
                {"paged": v_eng.page_stats()} if v_eng.paged else {}
            ),
            **_env_fields(),
        }

    # Truncated-depth draft sharing the target's weights: the cheapest
    # "small draft LM from models/lm.py" that exists without a second
    # training run. On random init its proposals barely correlate with
    # the target (acceptance is reported, not assumed); a trained
    # draft checkpoint slots into the same machinery via
    # scripts/serve.py --draft_checkpoint_dir.
    draft_spec = spec._replace(depth=max(1, depth // 2))
    draft_params = {
        k: params[k]
        for k in ["embed", "pos_embed", "ln_final"]
        + [f"block{i + 1}" for i in range(draft_spec.depth)]
    }
    variants = {
        "baseline": _variant("baseline", decode_attn="reference"),
        "flash_decode": _variant("flash_decode", decode_attn="auto"),
        "spec": _variant(
            "spec", decode_attn="auto",
            draft_spec=draft_spec, draft_params=draft_params,
            spec_tokens=4,
        ),
        # Perfectly-aligned draft (the target itself): acceptance-1.0
        # ceiling — measures the verify-round mechanics (γ tokens per
        # target step) with the draft-quality variable removed.
        "spec_selfdraft": _variant(
            "spec_selfdraft", decode_attn="auto",
            draft_spec=spec, draft_params=params, spec_tokens=4,
        ),
        "int8_kv": _variant("int8_kv", decode_attn="auto",
                            kv_dtype="int8"),
        # Paged KV (PR 12): page-pool cache + radix prefix index at
        # the capacity-neutral pool size. This traffic has no shared
        # prefixes, so the record measures the paged layout's pure
        # overhead (gather/scatter through the table); the reuse win
        # is serve_prefix's job.
        "paged_kv": _variant("paged_kv", decode_attn="auto",
                             page_size=16),
    }
    base_bytes = variants["baseline"]["cache_bytes_per_slot"]
    int8_bytes = variants["int8_kv"]["cache_bytes_per_slot"]
    assert int8_bytes <= 0.55 * base_bytes, (
        f"int8 KV cache bytes/slot {int8_bytes} did not halve the "
        f"fp32 layout {base_bytes}"
    )

    env = _env_fields()
    # Satellite 6 (stale on-chip trajectory): the provenance fields
    # are load-bearing for the next TPU-reachable capture — assert
    # they exist and agree before publishing, and say loudly when
    # this record is a CPU fallback.
    _assert_provenance(env)
    return {
        "metric": "serve_decode_throughput",
        "value": round(total_tokens / wall, 1),
        **env,
        **(
            {
                "note": "CPU-fallback capture: decode-path variant "
                "latencies are CPU-bound (flash-decode auto-selects "
                "the reference path off-TPU); not comparable with an "
                "on-chip record"
            }
            if env["cpu_fallback"]
            else {}
        ),
        "variants": variants,
        "flash_p50_vs_baseline": (
            round(
                variants["flash_decode"]["step_latency_s"]["p50"]
                / variants["baseline"]["step_latency_s"]["p50"],
                3,
            )
            if variants["baseline"]["step_latency_s"]["p50"]
            else None
        ),
        "int8_cache_bytes_ratio": round(int8_bytes / base_bytes, 3),
        # How many int8 lanes fit in the HBM one fp32 lane occupies —
        # the slots-per-chip capacity story.
        "int8_slots_capacity_gain": round(base_bytes / int8_bytes, 2),
        "mfu": _mfu(total_tokens / wall, fwd_per_token, device),
        "trace": trace,
        "engine_goodput": engine.goodput(),
        "unit": "tokens/sec/chip",
        "slots": slots,
        "prefill_len": prefill_len,
        "prefill_chunk": engine.prefill_chunk,
        "prefill_buckets": list(engine.buckets),
        "step_token_budget": engine.step_token_budget,
        "n_requests": n_requests,
        "rejected": rejected,
        "max_queue_depth": max_queue_depth,
        "arrival_rate_req_per_s": round(float(arrival_rate), 2),
        "ttft_s": engine.ttft.snapshot(),
        # User-facing latency percentiles (ISSUE 11): the perf
        # trajectory records what a user would see, not just step
        # latency — TTFT tail, median time-per-output-token, and the
        # queueing-delay tail the open-loop arrivals exist to build.
        "ttft_p99": (
            round(engine.ttft.percentile(99), 4)
            if engine.ttft.count else None
        ),
        "tpot_p50": (
            round(engine.tpot.percentile(50), 6)
            if engine.tpot.count else None
        ),
        "queue_s_p99": (
            round(engine.queue_wait.percentile(99), 4)
            if engine.queue_wait.count else None
        ),
        # Objectives evaluated over this run's traffic (recorded, not
        # asserted — see the SLOEngine note above) + request-trace
        # coverage: every completion reconstructed causally-ordered.
        "slo": slo.state(),
        "reqtrace": {
            "requests": len(timelines),
            "causal_ok": len(timelines),
        },
        "decode_tokens_per_s_per_req": engine.decode_rate.snapshot(),
        "step_latency_s": {
            "count": step_lat.count,
            "p50": (
                round(step_lat.percentile(50), 6)
                if step_lat.count else None
            ),
            "p99": (
                round(step_lat.percentile(99), 6)
                if step_lat.count else None
            ),
            "mean": (
                round(step_lat.snapshot(ndigits=6).get("mean", 0.0), 6)
                if step_lat.count
                else None
            ),
        },
        "compile_counts": compile_counts,
        "compile_budget": compile_budget,
        "promtext_samples": promtext_samples,
        "wall_s": round(wall, 3),
        "d_model": d,
        "depth": depth,
        "device_kind": getattr(device, "device_kind", "unknown"),
    }


def run_serve_prefix_bench(
    *,
    slots: int = 8,
    page_size: int = 16,
    prefix_tokens: int = 96,
    tail_tokens: int = 16,
    new_tokens: int = 32,
    n_requests: int = 24,
    seed: int = 0,
) -> dict:
    """Shared-prefix serving: the paged KV + radix index win (PR 12).

    The serve_decode entry's traffic shares nothing, so it measures
    the paged layout's overhead; THIS entry measures what the layout
    exists for. Open-loop traffic where every request shares one
    system prompt (``prefix_tokens``) and differs only in a short
    user tail — the fleet-routing regime PAPERS.md #1 identifies as
    where TPU serving loses to GPU baselines today. One seed request
    publishes the prefix pages; the rest fork them copy-free. The
    record carries:

    - token-level **prefix-hit rate** (matched prompt tokens /
      admitted prompt tokens) — the chunked prefill never runs for
      matched tokens, so this is the prefill-compute discount;
    - the **effective-slots multiplier**: peak Σ per-lane page
      mappings over unique mapped pages — how many lane-copies of
      residency the pool is serving per physical page (1.0 = the
      fixed-lane baseline, > 1 = the int8-compounding capacity win);
    - **TTFT p50/p99 split hit vs miss** — what reuse buys the user;
    - throughput vs a fixed-lane engine over the identical traffic
      (honest CPU nulls: off-TPU the gather/scatter overhead and the
      skipped prefill compute both land on the same cores).

    Both hit-rate and multiplier floors are asserted (>= 0.5 and
    > 1.5): they are scheduling facts, not timing facts, so a miss is
    a regression in the radix index, not noise.
    """
    import time

    import jax
    import numpy as np

    from ddp_tpu.models.lm import LMSpec, init_lm
    from ddp_tpu.serve.engine import ServeEngine
    from ddp_tpu.utils.metrics import StatSummary

    device = jax.devices()[0]
    vocab, d, depth, heads = 8192, 1024, 8, 8
    if device.platform != "tpu":
        # CPU fallback shape (the serve_decode convention): the
        # engine/index logic is platform-free; keep it minutes-cheap.
        vocab, d, depth, heads = 512, 128, 2, 4
        slots = min(slots, 4)
        prefix_tokens, tail_tokens = min(prefix_tokens, 48), 8
        new_tokens, n_requests = min(new_tokens, 16), min(n_requests, 12)
    prompt_len = prefix_tokens + tail_tokens
    total_len = prompt_len + new_tokens
    if total_len % page_size:
        total_len += page_size - total_len % page_size
    spec = LMSpec(
        vocab_size=vocab, total_len=total_len, d_model=d,
        depth=depth, num_heads=heads,
    )
    params = init_lm(spec, seed=0)
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, prefix_tokens).tolist()
    prompts = [
        prefix + rng.integers(0, vocab, tail_tokens).tolist()
        for _ in range(n_requests)
    ]

    def _drive(eng) -> dict:
        """Identical traffic shape per engine: the first request runs
        alone (on the paged engine it publishes the prefix), then the
        rest arrive as a burst — concurrent lanes really fork."""
        eng.warmup()
        counts = eng.compile_counts()
        t0 = time.perf_counter()
        rids = [eng.submit(prompts[0], new_tokens).request.rid]
        eng.run()
        eff_peak = None
        for p in prompts[1:]:
            adm = eng.submit(p, new_tokens)
            assert adm.accepted, adm.reason
            rids.append(adm.request.rid)
        while eng.pending:
            eng.step()
            ps = eng.page_stats()
            if ps and ps["effective_slots_multiplier"] is not None:
                eff_peak = max(
                    eff_peak or 0.0, ps["effective_slots_multiplier"]
                )
        wall = time.perf_counter() - t0
        assert eng.compile_counts() == counts, (
            "serve_prefix recompiled after warmup"
        )
        hit_ttft, miss_ttft = StatSummary(), StatSummary()
        tokens = 0
        for r in rids:
            c = eng.result(r)
            assert c is not None and c.status == "complete", (
                r, None if c is None else c.status
            )
            tokens += len(c.tokens)
            if c.ttft is None:
                continue
            # Fixed-lane completions carry prefix_hit_tokens=None —
            # no prefix cache means EVERY request pays the miss path,
            # so the control's TTFTs all land in the miss summary
            # (ttft_hit_s stays count-0 there by construction).
            if c.prefix_hit_tokens:
                hit_ttft.add(c.ttft)
            else:
                miss_ttft.add(c.ttft)

        def pct(s, q):
            return round(s.percentile(q), 4) if s.count else None

        return {
            "tokens_per_s": round(tokens / wall, 1),
            "total_tokens": tokens,
            "wall_s": round(wall, 3),
            "ttft_hit_s": {
                "count": hit_ttft.count,
                "p50": pct(hit_ttft, 50), "p99": pct(hit_ttft, 99),
            },
            "ttft_miss_s": {
                "count": miss_ttft.count,
                "p50": pct(miss_ttft, 50), "p99": pct(miss_ttft, 99),
            },
            "effective_slots_multiplier_peak": eff_peak,
            **(
                {"paged": eng.page_stats()} if eng.paged else {}
            ),
        }

    paged_eng = ServeEngine(
        spec, params, slots=slots, prefill_len=prompt_len,
        max_queue=max(16, n_requests), page_size=page_size,
    )
    paged = _drive(paged_eng)
    baseline = _drive(
        ServeEngine(
            spec, params, slots=slots, prefill_len=prompt_len,
            max_queue=max(16, n_requests),
        )
    )
    hit_rate = paged["paged"]["prefix_hit_rate"]
    eff = paged["effective_slots_multiplier_peak"]
    # Scheduling facts, not timing facts (see docstring) — assert.
    assert hit_rate is not None and hit_rate >= 0.5, (
        f"prefix hit rate {hit_rate} below the 0.5 floor on a "
        "shared-prefix workload: radix matching is broken"
    )
    assert eff is not None and eff > 1.5, (
        f"effective-slots multiplier {eff} never exceeded 1.5 with "
        f"{slots} lanes forking a {prefix_tokens}-token prefix: page "
        "sharing is broken"
    )
    env = _env_fields()
    _assert_provenance(env)
    return {
        "metric": "serve_prefix_hit_rate",
        "value": hit_rate,
        **env,
        **(
            {
                "note": "CPU-fallback capture: wall-clock numbers are "
                "honest CPU nulls (skipped prefill compute and table "
                "gather overhead share the same cores); hit rate and "
                "effective-slots multiplier are platform-free facts"
            }
            if env["cpu_fallback"]
            else {}
        ),
        "effective_slots_multiplier_peak": eff,
        "paged_vs_baseline_tokens_per_s": (
            round(paged["tokens_per_s"] / baseline["tokens_per_s"], 3)
            if baseline["tokens_per_s"]
            else None
        ),
        "paged_kv": paged,
        "fixed_lane_baseline": baseline,
        "unit": "hit fraction",
        "slots": slots,
        "page_size": page_size,
        "kv_pages": paged_eng.kv_pages,
        "prefix_tokens": prefix_tokens,
        "tail_tokens": tail_tokens,
        "new_tokens": new_tokens,
        "n_requests": n_requests,
        "total_len": total_len,
        "device_kind": getattr(device, "device_kind", "unknown"),
    }


def run_serve_fleet_bench(
    *,
    n_replicas: int = 3,
    slots: int = 4,
    page_size: int = 16,
    prefix_tokens: int = 48,
    tail_tokens: int = 8,
    new_tokens: int = 8,
    groups: int = 4,
    per_group: int = 6,
    kill_at: int = 12,
) -> dict:
    """Fleet serving (ISSUE 14): a REAL ≥3-replica CPU fleet —
    subprocess ``scripts/serve.py --init_demo`` engines behind the
    serve/fleet.py router — under open-loop shared-prefix traffic.

    Three phases over one fleet (distinct prefix sets, so the radix
    caches never cross-pollinate):

    1. **random dispatch** (the control): per-replica prefix-hit
       rates when traffic sprays everywhere;
    2. **prefix affinity**: the same traffic shape routed by the
       prompt-hash → preferred-replica map — the AFFINITY hit rate
       MUST beat the random one (asserted: it is a routing fact, not
       a timing fact), plus aggregate tokens/s and p99 TTFT;
    3. **kill drill**: ``kill:replica1@request<kill_at>`` mid-burst —
       ALL submitted requests complete (zero dropped, ASSERTED), no
       completion is delivered twice (fleet trace-id uniqueness,
       ASSERTED), exactly one replica restart (ASSERTED), replayed
       requests recorded, and recovery time measured from the
       SIGKILL to the first completion the restarted replica serves.

    Disaggregation phases (PR 16) over the same fleet:

    4. **prefill:decode ratio sweep** — 1:2, 1:1 and 2:1 tier splits
       (roles are router-side, so the sweep re-labels the live
       replicas) vs the homogeneous hybrid control, all under the
       same long-prompt traffic shape: aggregate tokens/s and p99
       TTFT per ratio (honest CPU nulls — replicas share cores),
       page-migration latency p50/p99 from the router's own summary,
       and zero dropped requests per ratio (ASSERTED);
    5. **migration token identity** — the same prompts asked of the
       1:2 disagg fleet and of the hybrid control must stream
       IDENTICAL tokens (ASSERTED; greedy over identical weights —
       disaggregation is a placement change, not a numerics change),
       with every disagg response served by the decode tier
       (ASSERTED);
    6. **directory vs affinity under churn** — seed shared prefixes,
       kill the affinity home of at least one group, serve through
       the outage, then burst after the restart: the fleet-wide
       prefix-hit rate with the prefix directory on must be >= the
       affinity-only control's (ASSERTED — the directory re-warms
       the restarted replica by pulling pages from the replica that
       served during the outage; affinity alone restarts cold).
    """
    import tempfile
    import threading
    import time
    import urllib.request

    import numpy as np

    from ddp_tpu.serve.fleet import (
        ROLE_DECODE,
        ROLE_HYBRID,
        ROLE_PREFILL,
        FleetChaos,
        ReplicaManager,
        Router,
        RouterConfig,
        affinity_key,
    )

    rng = np.random.default_rng(0)
    vocab, seq_len = 256, 128
    n_requests = groups * per_group

    def make_prompts(phase_seed):
        prng = np.random.default_rng(phase_seed)
        prefixes = [
            prng.integers(0, vocab, prefix_tokens).tolist()
            for _ in range(groups)
        ]
        return [
            prefixes[g] + prng.integers(0, vocab, tail_tokens).tolist()
            for g in range(groups)
            for _ in range(per_group)
        ]

    def paged_counts(url):
        with urllib.request.urlopen(url + "/statusz", timeout=10) as r:
            pg = json.loads(r.read()).get("stats", {}).get("paged") or {}
        return (
            int(pg.get("prefix_hits") or 0),
            int(pg.get("prefix_misses") or 0),
            pg.get("prefix_hit_rate"),
        )

    def drive(router, prompts):
        """Per-group seeding request first (publishes the prefix),
        then the open-loop burst — the serve_prefix traffic shape at
        fleet scale."""
        results: list[dict] = []
        lock = threading.Lock()

        def one(i):
            status, payload = router.dispatch(
                {
                    "prompt_tokens": prompts[i],
                    "max_new_tokens": new_tokens,
                }
            )
            with lock:
                # http_status is OURS; the payload's own "status" is
                # the completion status ("complete"/"timeout_...").
                results.append(
                    {"i": i, "http_status": status, **payload}
                )

        t0 = time.perf_counter()
        seed_threads = [
            threading.Thread(target=one, args=(g * per_group,))
            for g in range(groups)
        ]
        for t in seed_threads:
            t.start()
        for t in seed_threads:
            t.join()
        rest = [
            i for i in range(len(prompts)) if i % per_group != 0
        ]
        threads = [
            threading.Thread(target=one, args=(i,)) for i in rest
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return results, wall

    def phase_summary(results, wall):
        from ddp_tpu.utils.metrics import StatSummary

        ttft = StatSummary()
        tokens = 0
        for r in results:
            tokens += len(r.get("tokens") or [])
            if r.get("ttft_s") is not None:
                ttft.add(r["ttft_s"])
        return {
            "completed": sum(
                1 for r in results if r["http_status"] == 200
            ),
            "tokens_per_s": round(tokens / wall, 2) if wall else None,
            "total_tokens": tokens,
            "wall_s": round(wall, 3),
            "ttft_p50_s": (
                round(ttft.percentile(50), 4) if ttft.count else None
            ),
            "ttft_p99_s": (
                round(ttft.percentile(99), 4) if ttft.count else None
            ),
        }

    workdir = tempfile.mkdtemp(prefix="ddp_tpu_fleet_bench_")
    mgr = ReplicaManager(
        n_replicas,
        [
            "--init_demo",
            "--slots", str(slots),
            "--page_size", str(page_size),
            "--vocab_size", str(vocab),
            "--seq_len", str(seq_len),
        ],
        workdir=workdir,
        # Budget for the kill drill (1 restart) plus one kill per
        # churn trial in phase 6, even if they all land on replica 1.
        max_restarts=4,
        restart_backoff=0.2,
    )
    record: dict = {"metric": "serve_fleet_affinity_hit_rate"}
    try:
        mgr.start()
        assert mgr.wait_healthy(420), "fleet never became healthy"
        urls = [r.url for r in mgr.replicas]

        def hit_deltas(before):
            # Re-read replica URLs: a restarted replica (phase 6
            # churn) rebinds a fresh port, so the startup list goes
            # stale the moment a kill drill fires.
            after = [
                paged_counts(r.url) for r in mgr.replicas
            ]
            per_replica = []
            hits = misses = 0
            for (h0, m0, _), (h1, m1, rate) in zip(before, after):
                dh, dm = h1 - h0, m1 - m0
                hits += dh
                misses += dm
                per_replica.append(
                    {
                        "hits": dh, "misses": dm,
                        "hit_rate": (
                            round(dh / (dh + dm), 4)
                            if dh + dm
                            else None
                        ),
                        "lifetime_hit_rate": rate,
                    }
                )
            total = hits + misses
            return (
                round(hits / total, 4) if total else None,
                per_replica,
                after,
            )

        # Phase 1: random dispatch (the control the affinity claim
        # is measured against).
        base = [paged_counts(u) for u in urls]
        router = mgr.attach_router(
            Router(
                mgr.replicas,
                RouterConfig(affinity=False, trace_seed=1),
            )
        )
        results_r, wall_r = drive(router, make_prompts(101))
        random_rate, random_per_replica, base = hit_deltas(base)

        # Phase 2: prefix affinity (distinct prefixes — no help from
        # phase 1's published pages).
        router = mgr.attach_router(
            Router(
                mgr.replicas,
                RouterConfig(
                    affinity=True,
                    affinity_page=page_size,
                    trace_seed=2,
                ),
            )
        )
        results_a, wall_a = drive(router, make_prompts(202))
        affinity_rate, affinity_per_replica, base = hit_deltas(base)

        # Phase 3: the kill drill.
        chaos = FleetChaos(f"kill:replica1@request{kill_at}", mgr)
        kill_time = [None]
        orig_kill = mgr.kill_replica

        def timed_kill(index):
            kill_time[0] = time.perf_counter()
            orig_kill(index)

        mgr.kill_replica = timed_kill
        router = mgr.attach_router(
            Router(
                mgr.replicas,
                RouterConfig(
                    affinity=True,
                    affinity_page=page_size,
                    retry_backoff_s=0.02,
                    trace_seed=3,
                ),
                on_dispatch=chaos.on_dispatch,
            )
        )
        results_k, wall_k = drive(router, make_prompts(303))
        assert mgr.chaos_kills == 1, "the drill never fired"
        # zero dropped, zero duplicated — ASSERTED
        dropped = [
            r for r in results_k if r["http_status"] != 200
        ]
        assert not dropped, f"kill drill dropped {len(dropped)} requests"
        tids = [r["router"]["trace_id"] for r in results_k]
        assert len(set(tids)) == len(results_k), (
            "duplicate completion delivered (trace-id collision)"
        )
        # The non-vacuous half of zero-dup: (replica, replica-rid)
        # names the REPLICA-SIDE completion each response came from —
        # a collision would mean one engine completion was delivered
        # to two clients (a replayed/hedged response double-served).
        served = [
            (r["router"]["replica"], r.get("rid")) for r in results_k
        ]
        assert len(set(served)) == len(results_k), (
            "one replica completion was delivered twice"
        )
        # exactly one replica restart
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if mgr.restarts_total == 1 and all(
                r.state == "healthy" for r in mgr.replicas
            ):
                break
            time.sleep(0.25)
        assert mgr.restarts_total == 1, (
            f"expected exactly one restart, saw {mgr.restarts_total}"
        )
        # recovery time: SIGKILL → first completion the RESTARTED
        # replica serves (trickle until the router hands it one).
        recovery_s = None
        probe_deadline = time.monotonic() + 120
        while time.monotonic() < probe_deadline:
            status, payload = router.dispatch(
                {
                    "prompt_tokens": rng.integers(
                        0, vocab, page_size
                    ).tolist(),
                    "max_new_tokens": 2,
                }
            )
            if (
                status == 200
                and payload["router"]["replica"] == 1
            ):
                recovery_s = time.perf_counter() - kill_time[0]
                break
            time.sleep(0.2)
        kill_drill = {
            **phase_summary(results_k, wall_k),
            "killed_replica": 1,
            "kill_at_request": kill_at,
            "replays_total": router.replays_total,
            "retries_total": router.retries_total,
            "restarts": mgr.restarts_total,
            "recovery_s": (
                round(recovery_s, 3) if recovery_s else None
            ),
            "dropped": 0,
            "duplicated": 0,
        }
        # Phase 3 was the last chaos-wrapped phase; phase 6 kills
        # replicas directly.
        mgr.kill_replica = orig_kill

        # Phase 4: prefill:decode ratio sweep vs the hybrid control.
        # Roles are ROUTER-side placement over identical replica
        # processes, so the sweep re-labels the live fleet — the same
        # assignment `scripts/fleet.py --roles` makes at spawn time.
        # saturation_depth is raised because the decode tier shrinks
        # to 1-2 replicas: excess burst queues on the replicas
        # instead of tripping the router's spill/503 path.
        cutoff = 2 * page_size  # prefix traffic classifies prefill

        def set_roles(roles):
            for rep in mgr.replicas:
                rep.role = ROLE_HYBRID
            for rep, role in zip(mgr.replicas, roles):
                rep.role = role

        def disagg_counters(router):
            ms = router.migration_seconds
            return {
                "prefill_handoffs": router.prefill_handoffs_total,
                "migrations": router.migrations_total,
                "migration_failures": (
                    router.migration_failures_total
                ),
                "pages_migrated": router.pages_migrated_total,
                "migration_p50_s": (
                    round(ms.percentile(50), 4) if ms.count else None
                ),
                "migration_p99_s": (
                    round(ms.percentile(99), 4) if ms.count else None
                ),
            }

        ratio_sweep = {}
        for label, roles, seed in (
            ("1:2", [ROLE_PREFILL, ROLE_DECODE, ROLE_DECODE], 404),
            ("1:1", [ROLE_PREFILL, ROLE_DECODE], 414),
            ("2:1", [ROLE_PREFILL, ROLE_PREFILL, ROLE_DECODE], 424),
        ):
            set_roles(roles)
            subset = mgr.replicas[: len(roles)]
            router = mgr.attach_router(
                Router(
                    subset,
                    RouterConfig(
                        affinity=True,
                        affinity_page=page_size,
                        saturation_depth=64,
                        retry_max=5,
                        disagg=True,
                        prefill_cutoff_tokens=cutoff,
                        trace_seed=seed,
                    ),
                )
            )
            results, wall = drive(router, make_prompts(seed))
            dropped = sum(
                1 for r in results if r["http_status"] != 200
            )
            assert not dropped, (
                f"ratio {label} dropped {dropped} requests"
            )
            prefill_idx = {
                r.index for r in subset if r.role == ROLE_PREFILL
            }
            assert all(
                r["router"]["replica"] not in prefill_idx
                for r in results
            ), f"ratio {label}: client stream on the prefill tier"
            ratio_sweep[label] = {
                **phase_summary(results, wall),
                "roles": roles,
                **disagg_counters(router),
            }
        # Homogeneous control: same traffic shape, no tiers.
        set_roles([])
        router = mgr.attach_router(
            Router(
                mgr.replicas,
                RouterConfig(
                    affinity=True,
                    affinity_page=page_size,
                    saturation_depth=64,
                    retry_max=5,
                    trace_seed=434,
                ),
            )
        )
        results_h, wall_h = drive(router, make_prompts(434))
        assert all(r["http_status"] == 200 for r in results_h)
        hybrid_control = phase_summary(results_h, wall_h)

        # Phase 5: migration token identity — the SAME prompts asked
        # of the 1:2 disagg split and of the hybrid control must
        # stream identical tokens (greedy over identical weights).
        probe = make_prompts(606)[::per_group]
        set_roles([ROLE_PREFILL, ROLE_DECODE, ROLE_DECODE])
        router = mgr.attach_router(
            Router(
                mgr.replicas,
                RouterConfig(
                    affinity=True,
                    affinity_page=page_size,
                    disagg=True,
                    prefill_cutoff_tokens=cutoff,
                    trace_seed=606,
                ),
            )
        )
        disagg_streams = []
        for p in probe:
            status, payload = router.dispatch(
                {"prompt_tokens": p, "max_new_tokens": new_tokens}
            )
            assert status == 200, payload
            assert payload["router"]["replica"] != 0, (
                "identity probe served by the prefill tier"
            )
            disagg_streams.append(payload["tokens"])
        identity_counters = disagg_counters(router)
        assert identity_counters["migrations"] >= 1, (
            "identity probes never migrated pages"
        )
        set_roles([])
        router = mgr.attach_router(
            Router(
                mgr.replicas,
                RouterConfig(
                    affinity=True,
                    affinity_page=page_size,
                    trace_seed=616,
                ),
            )
        )
        for p, want in zip(probe, disagg_streams):
            status, payload = router.dispatch(
                {"prompt_tokens": p, "max_new_tokens": new_tokens}
            )
            assert status == 200, payload
            assert payload["tokens"] == want, (
                "migrated stream diverged from the hybrid stream"
            )

        # Phase 6: prefix directory vs affinity-only under churn.
        # Both trials: seed each group's prefix on its affinity home,
        # SIGKILL a home replica, serve through the outage (with the
        # directory on, completions re-home each prefix to whoever
        # served it), then burst once the victim restarts COLD.
        def churn_trial(seed, use_directory):
            prompts = make_prompts(seed)
            router = mgr.attach_router(
                Router(
                    mgr.replicas,
                    RouterConfig(
                        affinity=True,
                        affinity_page=page_size,
                        retry_backoff_s=0.02,
                        directory=use_directory,
                        trace_seed=seed,
                    ),
                )
            )
            leaders = list(range(0, len(prompts), per_group))
            for i in leaders:
                status, payload = router.dispatch(
                    {
                        "prompt_tokens": prompts[i],
                        "max_new_tokens": new_tokens,
                    }
                )
                assert status == 200, payload
            # Kill a replica that IS a group's affinity home, so the
            # burst below actually exercises the cold-restart case.
            homes = {
                affinity_key(prompts[i], page_size)
                % len(mgr.replicas)
                for i in leaders
            }
            victim = min(homes)
            r0 = mgr.restarts_total
            mgr.kill_replica(victim)
            deadline = time.monotonic() + 60
            while (
                mgr.replicas[victim].state == "healthy"
                and time.monotonic() < deadline
            ):
                time.sleep(0.05)
            for i in leaders:
                status, payload = router.dispatch(
                    {
                        "prompt_tokens": prompts[i],
                        "max_new_tokens": new_tokens,
                    }
                )
                assert status == 200, payload
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                if mgr.restarts_total > r0 and all(
                    r.state == "healthy" for r in mgr.replicas
                ):
                    break
                time.sleep(0.25)
            assert (
                mgr.replicas[victim].state == "healthy"
            ), "churn victim never came back"
            base = [paged_counts(r.url) for r in mgr.replicas]
            results, wall = drive(router, prompts)
            assert all(
                r["http_status"] == 200 for r in results
            ), "churn burst dropped requests"
            rate, per_rep, _ = hit_deltas(base)
            out = {
                **phase_summary(results, wall),
                "victim": victim,
                "post_churn_hit_rate": rate,
                "per_replica": per_rep,
            }
            if use_directory:
                pulls = router.directory_pulls_total
                hits = router.directory_pull_hits_total
                out["directory_pulls"] = pulls
                out["directory_pull_hits"] = hits
                out["directory_pull_hit_rate"] = (
                    round(hits / pulls, 4) if pulls else None
                )
            return out

        churn_affinity = churn_trial(808, use_directory=False)
        churn_directory = churn_trial(909, use_directory=True)
        assert (
            churn_directory["post_churn_hit_rate"] is not None
            and churn_affinity["post_churn_hit_rate"] is not None
        )
        assert (
            churn_directory["post_churn_hit_rate"]
            >= churn_affinity["post_churn_hit_rate"]
        ), (
            f"directory hit rate "
            f"{churn_directory['post_churn_hit_rate']} under churn "
            f"fell below the affinity-only control "
            f"{churn_affinity['post_churn_hit_rate']}: the prefix "
            "tier is not re-warming restarted replicas"
        )

        # Phase 7: fleet-wide distributed tracing (ISSUE 19) — a
        # FRESH 3-process disagg fleet launched with trace_dir, so
        # every replica exports its request spans on shutdown and the
        # router records a span per hop. The merged router+replica
        # trace dirs must reconstruct ONE causally-valid timeline per
        # request under a single trace id, prefill handoff and
        # /pages migration hops included (the acceptance gate).
        import glob as _glob
        import subprocess
        import sys as _sys

        from ddp_tpu.obs.reqtrace import (
            reconstruct_fleet,
            validate_fleet_timeline,
        )
        from ddp_tpu.obs.tracer import Tracer

        trace_root = os.path.join(workdir, "fleet_trace")
        tmgr = ReplicaManager(
            n_replicas,
            [
                "--init_demo",
                "--slots", str(slots),
                "--page_size", str(page_size),
                "--vocab_size", str(vocab),
                "--seq_len", str(seq_len),
            ],
            workdir=os.path.join(workdir, "trace_fleet"),
            max_restarts=1,
            restart_backoff=0.2,
            roles=[ROLE_PREFILL, ROLE_DECODE, ROLE_DECODE],
            trace_dir=trace_root,
        )
        fleet_tracer = Tracer(enabled=True)
        tprobe = make_prompts(707)[::per_group]
        try:
            tmgr.start()
            assert tmgr.wait_healthy(420), (
                "trace fleet never became healthy"
            )
            trouter = tmgr.attach_router(
                Router(
                    tmgr.replicas,
                    RouterConfig(
                        affinity=True,
                        affinity_page=page_size,
                        disagg=True,
                        prefill_cutoff_tokens=cutoff,
                        trace_seed=707,
                    ),
                    tracer=fleet_tracer,
                )
            )
            traced = []
            for p in tprobe:
                status, payload = trouter.dispatch(
                    {"prompt_tokens": p, "max_new_tokens": new_tokens}
                )
                assert status == 200, payload
                traced.append(payload["router"])
            # Per-hop seconds on the router digest — queue/dispatch on
            # every request, handoff/migrate on at least one.
            for d in traced:
                hops = d.get("hops") or {}
                assert "queue_s" in hops and "dispatch_s" in hops, d
            migrated_digests = [
                d for d in traced if "migrate_s" in d.get("hops", {})
            ]
            assert migrated_digests, (
                "trace phase never migrated pages — no migration hop "
                "to validate"
            )
            tstate = trouter.state()
            assert (
                tstate.get("trace_propagated_total") == len(tprobe)
            ), tstate
            assert "dispatch" in (tstate.get("hop_seconds") or {}), (
                tstate
            )
        finally:
            # Graceful drain, not the default 0.1s SIGKILL: each
            # replica exports its trace file on the SIGTERM path, and
            # a killed process exports nothing.
            tmgr.stop(drain_timeout=60)
        fleet_tracer.export_to_dir(os.path.join(trace_root, "router"))
        trace_dirs = [os.path.join(trace_root, "router")] + sorted(
            _glob.glob(os.path.join(trace_root, "replica*"))
        )
        assert len(trace_dirs) == n_replicas + 1, trace_dirs
        merged_path = os.path.join(trace_root, "merged.trace.json")
        proc = subprocess.run(
            [
                _sys.executable,
                os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "scripts", "trace_merge.py",
                ),
                *trace_dirs, "-o", merged_path,
            ],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        with open(merged_path) as f:
            merged_doc = json.load(f)
        fleet_side = merged_doc["ddp_tpu"].get("fleet") or {}
        assert fleet_side.get("count") == len(tprobe), fleet_side
        assert fleet_side.get("causal_ok") == len(tprobe), fleet_side
        assert fleet_side.get("migrated", 0) >= 1, fleet_side
        # The single-trace-id gate, re-derived from raw events: the
        # migrated request's router hop chain and its replica
        # admit→retire timeline reconstruct under ONE id and pass
        # causal validation (dispatch before admit, export before
        # install, exactly one winning decode path).
        fleet_map = reconstruct_fleet(merged_doc["traceEvents"])
        mig_tid = migrated_digests[0]["trace_id"]
        assert mig_tid in fleet_map, (mig_tid, sorted(fleet_map))
        mig_summary = validate_fleet_timeline(fleet_map[mig_tid])
        assert mig_summary["migrated"], mig_summary
        fleet_trace = {
            "requests": len(tprobe),
            "causal_ok": fleet_side["causal_ok"],
            "migrated": fleet_side["migrated"],
            "hop_p99_s": fleet_side.get("hop_p99_s"),
            "validated_trace_id": mig_tid,
            "winner_replica": mig_summary["winner_replica"],
        }

        # The headline assert: affinity must beat random dispatch on
        # per-replica prefix-hit rate — the reason the router hashes
        # prompts at all. A routing fact, not a timing fact.
        assert affinity_rate is not None and random_rate is not None
        assert affinity_rate > random_rate, (
            f"affinity hit rate {affinity_rate} does not beat random "
            f"{random_rate}: prefix affinity is not keeping replica "
            "caches warm"
        )
        env = _env_fields()
        _assert_provenance(env)
        record.update(
            value=affinity_rate,
            **env,
            unit="hit fraction",
            random_dispatch_hit_rate=random_rate,
            affinity_hit_rate=affinity_rate,
            per_replica_random=random_per_replica,
            per_replica_affinity=affinity_per_replica,
            random_dispatch=phase_summary(results_r, wall_r),
            affinity=phase_summary(results_a, wall_a),
            kill_drill=kill_drill,
            disagg_ratio_sweep=ratio_sweep,
            disagg_hybrid_control=hybrid_control,
            disagg_prefill_cutoff_tokens=cutoff,
            disagg_token_identity={
                "prompts": len(probe),
                "identical": True,
                **identity_counters,
            },
            churn_affinity_only=churn_affinity,
            churn_directory=churn_directory,
            fleet_trace=fleet_trace,
            n_replicas=n_replicas,
            slots=slots,
            page_size=page_size,
            prefix_tokens=prefix_tokens,
            tail_tokens=tail_tokens,
            new_tokens=new_tokens,
            n_requests_per_phase=n_requests,
            **(
                {
                    "note": "CPU-fallback capture: throughput/TTFT "
                    "(ratio sweep included) are honest CPU nulls "
                    "(replicas share cores); hit rates, "
                    "replay/restart accounting, zero-drop/zero-dup, "
                    "migration token identity and the "
                    "directory-vs-affinity churn ordering are "
                    "platform-free facts"
                }
                if env["cpu_fallback"]
                else {}
            ),
        )
    finally:
        mgr.stop()
    return record


def run_serve_reload_bench(
    *,
    n_replicas: int = 3,
    slots: int = 4,
    inflight: int = 8,
    new_tokens: int = 16,
) -> dict:
    """Model lifecycle (ISSUE 20): verified atomic hot-swap vs the
    only pre-lifecycle upgrade path (``/rollz`` process churn), plus
    the streaming-restore TTFT claim — all against REAL
    ``scripts/serve.py`` subprocesses.

    1. **in-flight across a swap** — a burst straddles a single
       replica's ``POST /reload`` to a different checkpoint: EVERY
       request completes (zero dropped, ASSERTED — admission pauses,
       it never sheds) and the swap's verify/load/swap timings come
       from the reload response itself.
    2. **cold vs streaming TTFT** — the same non-trivial checkpoint
       served twice from fresh processes: spawn → first generated
       token, cold (restore THEN warmup, serial) vs
       ``--streaming_restore`` (restore I/O behind the XLA warmup
       compiles). Streaming MUST reach its first token sooner
       (ASSERTED: the overlap is min(restore, warmup) of real work,
       not a timing coin-flip).
    3. **fleet swap vs ``/rollz``** — the same 3-replica fleet
       upgraded both ways: ``reload_fleet`` (in-place swaps, zero
       process churn — respawns == 0 ASSERTED, every replica
       converges on the target version, ASSERTED) must beat a
       rolling restart's wall-clock (ASSERTED: a swap restores one
       checkpoint; a roll pays full process + jax + warmup per
       replica).
    """
    import socket
    import subprocess
    import sys
    import tempfile
    import threading
    import time
    import urllib.error
    import urllib.request

    import jax.numpy as jnp
    import optax

    from ddp_tpu.models.lm import LMSpec, init_lm
    from ddp_tpu.parallel.ddp import TrainState
    from ddp_tpu.serve.fleet import ReplicaManager
    from ddp_tpu.serve.lifecycle import model_version_token
    from ddp_tpu.train.checkpoint import CheckpointManager, save_lm_spec

    env = _env_fields()
    record: dict = {"metric": "serve_reload_swap_vs_roll", **env}
    workdir = tempfile.mkdtemp(prefix="ddp_tpu_reload_bench_")
    serve_py = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts", "serve.py"
    )

    def save_ckpt(directory, spec, seed):
        params = init_lm(spec, seed=seed)
        tx = optax.sgd(0.01)
        state = TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=tx.init(params), model_state={},
        )
        mgr = CheckpointManager(directory, async_save=False)
        mgr.save(0, state)
        mgr.close()
        save_lm_spec(directory, spec)

    def post(url, path, body, timeout=120.0):
        req = urllib.request.Request(
            url + path,
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")

    def spawn_serve(ckpt, port, extra=()):
        """scripts/serve.py subprocess → (proc, url, ready_s, lines).

        ``lines`` keeps draining stdout in the background so the
        streaming milestone JSON is parseable after the fact."""
        t0 = time.perf_counter()
        proc = subprocess.Popen(
            [
                sys.executable, serve_py,
                "--checkpoint_dir", ckpt,
                "--slots", str(slots),
                "--port", str(port),
                *extra,
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
            text=True,
            cwd=os.path.dirname(serve_py) + "/..",
        )
        ready = [None]
        lines: list[dict] = []
        started = threading.Event()

        def drain():
            for line in proc.stdout:
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                lines.append(obj)
                if "serving" in obj:
                    ready[0] = time.perf_counter() - t0
                    started.set()

        t = threading.Thread(target=drain, daemon=True)
        t.start()
        assert started.wait(600), "serve.py never printed startup JSON"
        return proc, f"http://127.0.0.1:{port}", t0, ready[0], lines

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    # The engine-logic spec (phases 1+3): minutes-cheap on CPU.
    small = LMSpec(
        vocab_size=512, total_len=128, d_model=128, depth=2, num_heads=4,
    )
    ckpt_a = os.path.join(workdir, "ckpt_a")
    ckpt_b = os.path.join(workdir, "ckpt_b")
    save_ckpt(ckpt_a, small, seed=0)
    save_ckpt(ckpt_b, small, seed=1)

    # Phase 1: in-flight burst straddling a single-replica hot-swap.
    proc, url, _, _, _ = spawn_serve(ckpt_a, free_port())
    try:
        results: list[tuple[int, dict]] = []
        lock = threading.Lock()

        def one(i):
            status, payload = post(
                url, "/generate",
                {
                    "prompt_tokens": [(7 * i + j) % 512 for j in range(16)],
                    "max_new_tokens": new_tokens,
                },
            )
            with lock:
                results.append((status, payload))

        threads = [
            threading.Thread(target=one, args=(i,))
            for i in range(inflight)
        ]
        for t in threads:
            t.start()
        time.sleep(0.15)  # land the reload mid-burst
        status, payload = post(
            url, "/reload", {"checkpoint_dir": ckpt_b}
        )
        for t in threads:
            t.join()
        assert status == 200 and payload.get("reloaded"), payload
        assert payload["model_version"] == model_version_token(ckpt_b, 0)
        completed = sum(1 for s, _ in results if s == 200)
        assert completed == inflight, (
            f"swap dropped {inflight - completed}/{inflight} "
            f"in-flight requests"
        )
        record["inflight_across_swap"] = {
            "submitted": inflight,
            "completed": completed,
            "completion_rate": 1.0,
            "verify_s": payload.get("verify_s"),
            "load_s": payload.get("load_s"),
            "swap_s": payload.get("swap_s"),
        }
    finally:
        proc.kill()
        proc.wait()

    # Phase 2: cold vs streaming TTFT on a checkpoint whose restore
    # is real work (tens of MB), from fresh processes — both pay the
    # same interpreter + jax import; the delta is the overlap.
    big = LMSpec(
        vocab_size=4096, total_len=160, d_model=512, depth=6,
        num_heads=8,
    )
    ckpt_big = os.path.join(workdir, "ckpt_big")
    save_ckpt(ckpt_big, big, seed=0)
    ttft = {}
    for mode, extra in [
        ("cold", ()),
        ("streaming", ("--streaming_restore", "--stream_layers", "1")),
    ]:
        proc, url, t0, ready_s, lines = spawn_serve(
            ckpt_big, free_port(), extra
        )
        try:
            status, payload = post(
                url, "/generate",
                {"prompt_tokens": [1, 2, 3, 4], "max_new_tokens": 1},
                timeout=600.0,
            )
            first_token_s = time.perf_counter() - t0
            assert status == 200 and payload.get("tokens"), payload
            ttft[mode] = {
                "ready_s": round(ready_s, 3),
                "first_token_s": round(first_token_s, 3),
            }
            if mode == "streaming":
                ms = [ln for ln in lines if ln.get("streamed")]
                if ms:
                    ttft[mode]["admission_ready_s"] = round(
                        ms[-1]["admission_ready_s"], 3
                    )
                    ttft[mode]["complete_s"] = round(
                        ms[-1]["complete_s"], 3
                    )
        finally:
            proc.kill()
            proc.wait()
    assert (
        ttft["streaming"]["first_token_s"] < ttft["cold"]["first_token_s"]
    ), f"streaming restore won nothing: {ttft}"
    record["ttft"] = ttft

    # Phase 3: the same fleet upgraded both ways — in-place swaps
    # (zero process churn) vs the PR-13 rolling restart.
    mgr = ReplicaManager(
        n_replicas,
        [
            "--checkpoint_dir", ckpt_a,
            "--slots", str(slots),
        ],
        workdir=os.path.join(workdir, "fleet"),
        max_restarts=2,
        restart_backoff=0.2,
    )
    try:
        mgr.start()
        assert mgr.wait_healthy(420), "fleet never became healthy"
        restarts_before = mgr.restarts_total
        t0 = time.perf_counter()
        out = mgr.reload_fleet(ckpt_b)
        swap_wall = time.perf_counter() - t0
        assert out["ok"], out
        assert out["respawns"] == 0, out
        assert mgr.restarts_total == restarts_before, (
            "hot-swap respawned a process"
        )
        target = model_version_token(ckpt_b, 0)
        # /healthz advertises the serving version via the poll loop —
        # give it a couple of poll intervals to observe the swap.
        deadline = time.monotonic() + 30
        versions = {r.model_version for r in mgr.replicas}
        while versions != {target} and time.monotonic() < deadline:
            time.sleep(0.25)
            versions = {r.model_version for r in mgr.replicas}
        assert versions == {target}, (
            f"fleet did not converge: {versions} != {{{target}}}"
        )
        t0 = time.perf_counter()
        roll = mgr.rolling_restart()
        roll_wall = time.perf_counter() - t0
        assert roll.get("ok", True), roll
        assert swap_wall < roll_wall, (
            f"hot-swap ({swap_wall:.2f}s) not faster than /rollz "
            f"({roll_wall:.2f}s)"
        )
        record["fleet_upgrade"] = {
            "replicas": n_replicas,
            "swap_wall_s": round(swap_wall, 3),
            "roll_wall_s": round(roll_wall, 3),
            "speedup": round(roll_wall / swap_wall, 2),
            "swap_respawns": 0,
            "converged_version": target,
        }
    finally:
        mgr.stop()

    _assert_provenance(env)
    record.update(
        **(
            {
                "null_result_note":
                    "CPU capture: wall-clocks are not chip numbers, "
                    "but zero-dropped, zero-respawn, version "
                    "convergence and the swap<roll / streaming<cold "
                    "orderings are platform-free facts"
            }
            if env["cpu_fallback"]
            else {}
        ),
    )
    return record


def run_loader_bench(
    *, n: int = 4096, side: int = 96, batch: int = 256, epochs: int = 3
) -> dict:
    """Native C++ worker pool vs single-thread Python gather.

    Two measurements (round-3 verdict weak #3 — "win or retire"):

    1. **Raw assembly race** — host-side batch gather only, no device
       work. On a 1-core host the pool LOSES this by construction
       (its ring adds a handoff on the same core that does the
       gather); that measurement is what sets the loader's
       auto-disable policy (data/loader.py POOL_MIN_BATCH_BYTES +
       the >1-core requirement).
    2. **Overlap regime** (TPU only — the pool's actual purpose): a
       training loop where the device computes step t while the host
       assembles batch t+1. The C++ workers release the GIL, so even
       on one host core they overlap the Python thread's blocking
       device wait — the reference's ``num_workers=2`` rationale
       (data.py:21-25). Reported as ``overlap_native_s`` vs
       ``overlap_python_s`` wall-clock for the same step count.

    ImageNet-shaped uint8 rows in both.
    """
    import time

    import numpy as np

    from ddp_tpu import native

    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(n, side, side, 3), dtype=np.uint8)
    labels = rng.integers(0, 1000, size=(n,)).astype(np.int32)
    idx = rng.permutation(n)
    steps = n // batch

    def python_gather():
        t0 = time.perf_counter()
        for _ in range(epochs):
            for b in range(steps):
                sel = idx[b * batch : (b + 1) * batch]
                _ = images[sel], labels[sel]
        return epochs * steps / (time.perf_counter() - t0)

    import os

    from ddp_tpu.data.loader import ShardedLoader

    batch_bytes = batch * side * side * 3
    pool_engaged = ShardedLoader.pool_would_engage(batch_bytes)
    result = {
        "metric": "loader_batch_assembly",
        **_env_fields(),
        "shape": [batch, side, side, 3],
        "python_batches_per_sec": round(python_gather(), 1),
        "native_available": native.available(),
        # The pool's win conditions are (a) >1 host core and (b)
        # overlap with device compute; a raw assembly race on a 1-core
        # box measures its ring overhead instead. Record the context
        # and what ShardedLoader's gate (bytes >= POOL_MIN_BATCH_BYTES
        # AND >1 core) would decide for this shape on this host.
        "cpu_count": os.cpu_count(),
        "pool_gate_would_engage": pool_engaged,
    }
    if native.available():
        pre = native.NativePrefetcher(images, labels, batch, num_workers=2)
        try:
            t0 = time.perf_counter()
            for _ in range(epochs):
                for _ in pre.epoch(idx):
                    pass
            result["native_batches_per_sec"] = round(
                epochs * steps / (time.perf_counter() - t0), 1
            )
            result["native_speedup"] = round(
                result["native_batches_per_sec"]
                / result["python_batches_per_sec"],
                2,
            )
        finally:
            pre.close()
    result.update(_loader_overlap_bench(images, labels, idx, batch))
    return result


def _loader_overlap_bench(images, labels, idx, batch, *, steps=24) -> dict:
    """Host-assembly ↔ device-compute overlap: the pool's real regime.

    Runs a small conv train step on the DEVICE while the host prepares
    the next batch — python gather vs the C++ ring. TPU only: on a CPU
    backend the 'device' computes on the same core as the loader, so
    there is no idle host time to overlap into and the measurement
    would just re-state the raw assembly race above.
    """
    import time

    import jax
    import jax.numpy as jnp
    import optax

    from ddp_tpu import native

    if jax.devices()[0].platform != "tpu" or not native.available():
        return {}
    # SimpleCNN is MNIST-shaped; a small generic conv step serves here.
    import flax.linen as nn

    side = images.shape[1]

    class TinyConv(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Conv(32, (3, 3))(x)
            x = nn.relu(x)
            x = nn.Conv(64, (3, 3), strides=(2, 2))(x)
            x = nn.relu(x).mean(axis=(1, 2))
            return nn.Dense(1000)(x)

    model = TinyConv()
    tx = optax.sgd(0.01)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, side, side, 3), jnp.float32)
    )["params"]
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, xb, yb):
        def loss_fn(p):
            logits = model.apply(
                {"params": p}, xb.astype(jnp.float32) / 255.0
            )
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, yb
            ).mean()

        loss, g = jax.value_and_grad(loss_fn)(params)
        up, opt = tx.update(g, opt, params)
        return optax.apply_updates(params, up), opt, loss

    def python_loop():
        p, o = params, opt
        t0 = time.perf_counter()
        for b in range(steps):
            sel = idx[(b * batch) % len(idx) : (b * batch) % len(idx) + batch]
            if len(sel) < batch:
                sel = idx[:batch]
            p, o, loss = step(p, o, jnp.asarray(images[sel]),
                              jnp.asarray(labels[sel]))
        jax.block_until_ready(loss)
        return time.perf_counter() - t0

    def native_loop():
        pre = native.NativePrefetcher(images, labels, batch, num_workers=2)
        try:
            p, o = params, opt
            t0 = time.perf_counter()
            done = 0
            while done < steps:
                for xb, yb in pre.epoch(idx):
                    p, o, loss = step(p, o, jnp.asarray(xb), jnp.asarray(yb))
                    done += 1
                    if done >= steps:
                        break
            jax.block_until_ready(loss)
            return time.perf_counter() - t0
        finally:
            pre.close()

    # Warm the compile outside both timed windows.
    _ = step(params, opt, jnp.asarray(images[idx[:batch]]),
             jnp.asarray(labels[idx[:batch]]))
    py_s = python_loop()
    nat_s = native_loop()
    return {
        "overlap_steps": steps,
        "overlap_python_s": round(py_s, 3),
        "overlap_native_s": round(nat_s, 3),
        "overlap_native_speedup": round(py_s / nat_s, 2),
    }


def _zero_bench_impl(
    *, batch_per_shard: int = 32, warmup_steps: int = 3,
    timed_steps: int = 20, bucket_mb: float = 0.05,
) -> dict:
    """ZeRO weight-update sharding vs the ddp baseline, world ≥ 2.

    Three step variants over identical data on the full device mesh:
    the ddp all-reduce step, the zero step (bucketed psum_scatter /
    1/N update / all_gather — scheduler free to overlap), and the
    zero step with its no-overlap control (optimization_barrier fence
    after backward + serial collective chain). Reports step-time p50,
    the analytic per-step collective payload (comm_bytes — the zero
    path's all_reduce term is ZERO, the headline claim), the
    optimizer-state memory high-water per device (live-buffer
    accounting over the real shardings — strictly 1/N for zero), and
    the MEASURED overlap fraction: the share of the serialized step
    time the scheduler hid by overlapping the bucketed collectives
    with compute, plus the obs/steptime dispatch-vs-compute split of
    one representative step of each variant. On a CPU backend the
    collectives share cores with compute, so expect the overlap
    fraction near zero there — the record states what was measured,
    not what the TPU scheduler would do.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddp_tpu.models import get_model
    from ddp_tpu.obs.steptime import dispatch_compute_split
    from ddp_tpu.parallel.ddp import (
        create_train_state,
        make_train_step,
        replicate_state,
    )
    from ddp_tpu.parallel.zero import (
        create_zero_state,
        ddp_comm_bytes,
        make_zero_train_step,
        opt_bytes_per_device,
        zero_comm_bytes,
    )
    from ddp_tpu.runtime.mesh import (
        MeshSpec, data_axes, make_mesh, slice_block_size,
    )
    from ddp_tpu.utils.metrics import StatSummary

    devices = jax.devices()
    world = len(devices)
    mesh = make_mesh(MeshSpec(data=world), devices=devices)
    model = get_model("simple_cnn")
    tx = optax.adam(1e-3)
    sample = jnp.zeros((1, 28, 28, 1))
    batch = batch_per_shard * world
    rng = np.random.default_rng(0)
    sh = NamedSharding(mesh, P(data_axes(mesh)))
    images_np = rng.integers(0, 256, (batch, 28, 28, 1), dtype=np.uint8)
    labels_np = rng.integers(0, 10, (batch,)).astype(np.int32)
    images = jax.device_put(images_np, sh)
    labels = jax.device_put(labels_np, sh)

    ddp_state = replicate_state(
        create_train_state(model, tx, sample, seed=0), mesh
    )
    zero_state, layout = create_zero_state(
        model, tx, sample, mesh, seed=0, bucket_mb=bucket_mb
    )
    bf16_state, bf16_layout = create_zero_state(
        model, tx, sample, mesh, seed=0, bucket_mb=bucket_mb,
        gather_dtype="bf16",
    )
    # Two emulated slices for the hierarchical variant (dcn outermost
    # — runtime/mesh.py): world must split 2×(world/2). At world 2 the
    # per-slice group would be 1 (nothing to scatter) — skipped with a
    # note rather than recorded as a vacuous number.
    hier_ok = world >= 4 and world % 2 == 0
    if hier_ok:
        hier_mesh = make_mesh(
            MeshSpec(dcn=2, data=world // 2), devices=devices
        )
        hsh = NamedSharding(hier_mesh, P(data_axes(hier_mesh)))
        h_images = jax.device_put(images_np, hsh)
        h_labels = jax.device_put(labels_np, hsh)
        hier_state, hier_layout = create_zero_state(
            model, tx, sample, hier_mesh, seed=0, bucket_mb=bucket_mb
        )
    # Each variant dispatches through the xprof compile ledger
    # (obs/xprof.py): the record then carries real compile seconds per
    # variant, the HBM high-water of the measured loops, and — the
    # cross-check this bench exists to keep honest — the HLO-derived
    # collective bytes next to the analytic comm_bytes estimates.
    from ddp_tpu.obs.xprof import DeviceMemorySampler, Xprof

    xprof = Xprof(enabled=True)
    hbm = DeviceMemorySampler(enabled=True)

    def zstep(lay, **kw):
        return make_zero_train_step(model, tx, mesh, lay, donate=False, **kw)

    # name -> (instrumented step, state, (images, labels))
    variants = {
        "ddp": (
            xprof.instrument(
                make_train_step(model, tx, mesh, donate=False), "ddp"
            ),
            ddp_state, (images, labels),
        ),
        "zero": (
            xprof.instrument(zstep(layout), "zero"),
            zero_state, (images, labels),
        ),
        "zero_serialized": (
            xprof.instrument(zstep(layout, overlap=False), "zero_serialized"),
            zero_state, (images, labels),
        ),
        "gather_bf16": (
            xprof.instrument(
                zstep(bf16_layout, gather_dtype="bf16"), "gather_bf16"
            ),
            bf16_state, (images, labels),
        ),
        "gather_bf16_serialized": (
            xprof.instrument(
                zstep(bf16_layout, gather_dtype="bf16", overlap=False),
                "gather_bf16_serialized",
            ),
            bf16_state, (images, labels),
        ),
    }
    if hier_ok:
        variants["hier"] = (
            xprof.instrument(
                make_zero_train_step(
                    model, tx, hier_mesh, hier_layout, donate=False
                ),
                "hier",
            ),
            hier_state, (h_images, h_labels),
        )
        variants["hier_serialized"] = (
            xprof.instrument(
                make_zero_train_step(
                    model, tx, hier_mesh, hier_layout, donate=False,
                    overlap=False,
                ),
                "hier_serialized",
            ),
            hier_state, (h_images, h_labels),
        )
    p50 = {}
    split = {}
    final_loss = {}
    for name, (step, state0, (imgs, lbls)) in variants.items():
        state = state0
        summary = StatSummary()
        for i in range(warmup_steps + timed_steps):
            t0 = time.perf_counter()
            state, metrics = step(state, imgs, lbls)
            jax.block_until_ready(metrics.loss)
            if i >= warmup_steps:
                summary.add(time.perf_counter() - t0)
        p50[name] = round(summary.percentile(50), 6)
        final_loss[name] = round(float(metrics.loss), 6)
        # obs/steptime attribution of one more step: dispatch-return
        # vs block_until_ready — the same split the trainer records.
        (_, m2), disp_s, comp_s, _ = dispatch_compute_split(
            step, state, imgs, lbls
        )
        split[name] = {
            "dispatch_s": round(disp_s, 6), "compute_s": round(comp_s, 6),
        }

    def overlap(fast, slow):
        return round(
            max(0.0, 1.0 - p50[fast] / max(p50[slow], 1e-9)), 4
        )

    overlap_fraction = overlap("zero", "zero_serialized")
    opt_mem = {
        "ddp": opt_bytes_per_device(ddp_state.opt_state),
        "zero": opt_bytes_per_device(zero_state.opt_state),
    }
    hbm.sample()
    comm_est = {
        "ddp": ddp_comm_bytes(ddp_state.params, world),
        "zero": zero_comm_bytes(layout, world),
    }
    # Hand ledger vs compiled program: ring-model traffic from the
    # optimized HLO's collective payloads, checked against the
    # analytic estimate each strategy publishes (parallel/zero.py).
    comm_check = {
        name: xprof.comm_check(name, comm_est[name]["total"], world)
        for name in ("ddp", "zero")
    }
    compile_s = {}
    for rec in xprof.ledger_records():
        compile_s[rec["label"]] = round(
            compile_s.get(rec["label"], 0.0) + rec["compile_time_s"], 3
        )

    # --- sub-records: the pod-scale comm variants, each with its own
    # analytic pricing, HLO cross-check, overlap control, and
    # provenance (gather dtype + the mesh's axis shape — what makes
    # BENCH_* comparisons across flat/hier captures greppable in one
    # field, like the platform/backend/cpu_fallback trio).
    def mesh_axes_of(m):
        return {a: int(s) for a, s in m.shape.items() if int(s) > 1}

    bf16_est = zero_comm_bytes(bf16_layout, world, gather_dtype="bf16")
    sub = {
        "gather_bf16": {
            "gather_dtype": "bf16",
            "mesh_axes": mesh_axes_of(mesh),
            "step_time_p50_s": p50["gather_bf16"],
            "dispatch_compute": split["gather_bf16"],
            "overlap_fraction": overlap(
                "gather_bf16", "gather_bf16_serialized"
            ),
            "comm_bytes": bf16_est,
            "hlo_comm_check": xprof.comm_check(
                "gather_bf16", bf16_est["total"], world
            ),
            "opt_state_bytes_per_device": opt_bytes_per_device(
                bf16_state.opt_state
            ),
            "final_loss": final_loss["gather_bf16"],
            "loss_delta_vs_ddp": round(
                abs(final_loss["gather_bf16"] - final_loss["ddp"]), 6
            ),
        },
    }
    # The headline byte claim, ASSERTED: half-width gathers move half
    # the all-gather bytes in the analytic model AND the compiled HLO.
    assert 2 * bf16_est["all_gather"] == comm_est["zero"]["all_gather"]
    bf16_check = sub["gather_bf16"]["hlo_comm_check"]
    zero_check = comm_check["zero"]
    if bf16_check and zero_check:
        ratio = bf16_check["measured_by_kind"]["all_gather"] / max(
            1, zero_check["measured_by_kind"]["all_gather"]
        )
        sub["gather_bf16"]["hlo_ag_ratio_vs_fp32"] = round(ratio, 4)
        assert abs(ratio - 0.5) < 0.05, (
            f"bf16 gather not half-width in HLO: {ratio}"
        )
    if hier_ok:
        hier_est = zero_comm_bytes(
            hier_layout, world // 2, dcn=2
        )
        flat_on_pod = zero_comm_bytes(
            hier_layout, world // 2, dcn=2, hier=False
        )
        hier_check = xprof.comm_check(
            "hier", hier_est["total"], world,
            expected_by_axis=hier_est["by_axis"],
            slice_size=slice_block_size(hier_mesh),
        )
        sub["hier"] = {
            "gather_dtype": "fp32",
            "mesh_axes": mesh_axes_of(hier_mesh),
            "step_time_p50_s": p50["hier"],
            "dispatch_compute": split["hier"],
            "overlap_fraction": overlap("hier", "hier_serialized"),
            "comm_bytes": hier_est,
            "flat_comm_bytes": flat_on_pod,
            "hlo_comm_check": hier_check,
            "opt_state_bytes_per_device": opt_bytes_per_device(
                hier_state.opt_state
            ),
            "final_loss": final_loss["hier"],
            "loss_delta_vs_ddp": round(
                abs(final_loss["hier"] - final_loss["ddp"]), 6
            ),
        }
        # Cross-slice bytes ≤ 1/N_data of the flat all-data traffic —
        # the hierarchy's reason to exist, asserted not narrated.
        assert (
            hier_est["by_axis"]["dcn"]["total"]
            <= flat_on_pod["total"] / (world // 2) + 64
        )
        if hier_check is not None:
            assert hier_check["within_tolerance"], hier_check
    else:
        sub["hier"] = {
            "skipped": f"world {world} < 4: a 2-slice mesh would have "
            "a 1-wide ICI group (nothing to scatter)",
        }
    for rec in sub.values():
        rec.setdefault("gather_dtype", None)
        rec.update(_env_fields())

    return {
        "metric": "zero_weight_update_sharding",
        **_env_fields(),
        "world_size": world,
        "bucket_mb": bucket_mb,
        "buckets": len(layout.buckets),
        "batch": batch,
        "timed_steps": timed_steps,
        "step_time_p50_s": p50,
        "dispatch_compute": split,
        "overlap_fraction": overlap_fraction,
        "comm_bytes": comm_est,
        "hlo_comm_check": comm_check,
        "compile_time_s": compile_s,
        "hbm_high_water_bytes": hbm.high_water_bytes,
        "opt_state_bytes_per_device": opt_mem,
        "opt_memory_ratio": round(
            opt_mem["zero"] / max(1, opt_mem["ddp"]), 4
        ),
        # One-step parity guard: a wrong sharded update would drift
        # the loss; the full pins live in tests/test_zero.py.
        "loss_delta_vs_ddp": round(
            abs(final_loss["zero"] - final_loss["ddp"]), 6
        ),
        "final_loss": final_loss,
        "variants": sub,
    }


def run_elastic_bench(*, timeout: float = 600.0) -> dict:
    """Elastic world-resize drill (ROADMAP item 4 / ISSUE 8) as a
    measured bench entry: a REAL 2-process spawn where rank 1 is
    permanently lost mid-epoch-1 (``--chaos shrink:rank1@step12``)
    under ``--elastic --min_world 1`` — the supervisor reaps the
    world, relaunches it one smaller, and the survivor resumes from
    the epoch-0 checkpoint at the preserved global batch.

    Reports **recovery-time p50**: fault → first post-resize optimizer
    step, measured from the metrics stream's wall clocks (the drill
    runs ``--log_interval 1`` so the last pre-fault record is at most
    one step stale; one drill = one sample, so p50 is that sample —
    the field name states the contract, ``recovery_samples`` states
    the honesty). Plus the **resize-downtime share** of the run's wall
    clock from the goodput sidecar's restart-vs-resize attribution,
    and ``lint_clean`` like the headline record. Always a CPU-spawn
    measurement by construction (``--spawn`` emulates hosts on CPU);
    the number is a *recovery-path latency*, not a throughput claim.
    """
    import os
    import subprocess
    import sys
    import tempfile

    work = tempfile.mkdtemp(prefix="ddp_tpu_elastic_bench_")
    ck = os.path.join(work, "ck")
    metrics_path = os.path.join(work, "metrics.jsonl")
    root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [
        sys.executable, os.path.join(root, "train.py"),
        "--spawn", "2", "--elastic", "--min_world", "1",
        "--epochs", "2", "--batch_size", "4",
        "--synthetic_data", "--synthetic_size", "64",
        "--eval_every", "0", "--log_interval", "1",
        "--checkpoint_dir", ck,
        "--data_root", os.path.join(work, "data"),
        "--metrics_file", metrics_path,
        "--chaos", "shrink:rank1@step12",
        "--restart_backoff", "0.1",
    ]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout,
            env=env, cwd=root,
        )
    except subprocess.TimeoutExpired:
        return {
            "metric": "elastic_world_resize",
            "platform": "cpu",
            "backend": "cpu",
            "cpu_fallback": True,
            "error": f"drill timed out after {timeout:.0f}s",
        }
    if proc.returncode != 0:
        return {
            "metric": "elastic_world_resize",
            "platform": "cpu",
            "backend": "cpu",
            "cpu_fallback": True,
            "error": f"drill rc={proc.returncode}: {proc.stderr[-800:]}",
        }
    records = []
    try:
        with open(metrics_path) as f:
            for line in f:
                try:
                    records.append(json.loads(line))
                except json.JSONDecodeError:
                    continue  # torn tail — same tolerance as triage
    except OSError:
        pass
    rs_idx = [
        i for i, r in enumerate(records) if r.get("kind") == "run_start"
    ]
    worlds = [records[i].get("data_shards") for i in rs_idx]
    resize_i = None
    for i in rs_idx:
        r = records[i]
        if (
            r.get("prev_data_shards")
            and r.get("data_shards") != r.get("prev_data_shards")
        ):
            resize_i = i
    recovery = None
    if resize_i is not None and resize_i > 0:
        fault_t = records[resize_i - 1].get("time")
        first_step = next(
            (
                r for r in records[resize_i:]
                if r.get("kind") == "step"
            ),
            None,
        )
        if fault_t and first_step:
            recovery = float(first_step["time"]) - float(fault_t)
    side = {}
    try:
        with open(os.path.join(ck, "goodput.json")) as f:
            side = json.load(f)
    except (OSError, ValueError):
        pass
    wall = max(
        1e-9,
        float(side.get("last_flush_unix", 0.0))
        - float(side.get("first_launch_unix", 0.0)),
    )
    resize_down = float(side.get("resize_downtime_s", 0.0))
    steps = [r for r in records if r.get("kind") == "step"]
    return {
        "metric": "elastic_world_resize",
        # --spawn emulates hosts on CPU by design: the drill is a
        # recovery-path latency on emulated hosts, never an on-chip
        # throughput claim — flagged like every other CPU capture.
        "platform": "cpu",
        "backend": "cpu",
        "cpu_fallback": True,
        "world_trajectory": worlds,
        "generations": len(rs_idx),
        "resizes": int(side.get("resizes", 0)),
        "restarts": int(side.get("restarts", 0)),
        "recovery_time_p50_s": (
            round(recovery, 3) if recovery is not None else None
        ),
        "recovery_samples": 1 if recovery is not None else 0,
        "resize_downtime_s": round(resize_down, 3),
        "restart_downtime_s": round(
            float(side.get("restart_downtime_s", 0.0)), 3
        ),
        "resize_downtime_share": round(resize_down / wall, 4),
        "final_step": max((r.get("step", 0) for r in steps), default=0),
        "lint_clean": _lint_clean(),
    }


def run_mpmd_bench(*, timeout: float = 600.0) -> dict:
    """MPMD pipeline runtime (ISSUE 17) vs the in-graph SPMD 1F1B
    control at identical shapes/seeds: a REAL 2-process-per-stage
    spawn (``parallel/mpmd.py``) against the single-program schedule
    on 2 emulated devices.

    Reports step-time p50/p99 and the measured bubble/p2p-wait
    fractions from the stage-tagged step records, per-stage compile
    seconds with the headline assertion of the subsystem — the SUM of
    the per-stage compiles stays below the SPMD single-program
    compile (each stage builds 1/K of the model) — loss-trajectory
    parity vs the control, and the ``kill:stage1`` drill's recovery
    time (fault → first post-restart step, one drill = one sample).
    Always a CPU-spawn measurement by construction; the numbers are
    schedule/recovery characteristics, not a throughput claim.
    """
    import os
    import subprocess
    import sys
    import tempfile

    from ddp_tpu.utils.metrics import StatSummary

    work = tempfile.mkdtemp(prefix="ddp_tpu_mpmd_bench_")
    root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    shape = [
        "--stages", "2", "--steps", "8", "--batch_size", "8",
        "--microbatches", "4", "--seq_len", "16", "--d_model", "32",
    ]
    base = [sys.executable, "-m", "ddp_tpu.parallel.mpmd", *shape]
    provenance = {
        "metric": "mpmd_pipeline_runtime",
        # one emulated CPU device per stage process by design: the
        # drill measures schedule/recovery behavior, never on-chip
        # throughput — flagged like every other CPU capture.
        "platform": "cpu",
        "backend": "cpu",
        "cpu_fallback": True,
    }

    def _fail(what: str, proc=None) -> dict:
        rec = dict(provenance)
        detail = what
        if proc is not None:
            detail += f" rc={proc.returncode}: {proc.stderr[-800:]}"
        rec["error"] = detail
        return rec

    def _records(path: str) -> list:
        out = []
        try:
            with open(path) as f:
                for line in f:
                    try:
                        out.append(json.loads(line))
                    except json.JSONDecodeError:
                        continue  # torn tail — same tolerance as triage
        except OSError:
            pass
        return out

    # 1) MPMD run (2 stage processes, supervised)
    metrics_path = os.path.join(work, "metrics.jsonl")
    mpmd_json = os.path.join(work, "mpmd.json")
    try:
        proc = subprocess.run(
            base + [
                "--workdir", os.path.join(work, "run"),
                "--metrics_file", metrics_path,
                "--json", mpmd_json,
            ],
            capture_output=True, text=True, timeout=timeout / 3,
            env=env, cwd=root,
        )
    except subprocess.TimeoutExpired:
        return _fail(f"mpmd run timed out after {timeout / 3:.0f}s")
    if proc.returncode != 0:
        return _fail("mpmd run", proc)
    try:
        with open(mpmd_json) as f:
            mpmd = json.load(f)
    except (OSError, ValueError) as e:
        return _fail(f"mpmd result unreadable: {e}")

    # 2) SPMD 1F1B control: same shapes, 2 emulated devices, ONE
    # program (the compile-cost baseline and the parity reference)
    ctl_env = dict(env)
    ctl_env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    ).strip()
    ctl_env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    ctl_json = os.path.join(work, "control.json")
    try:
        proc = subprocess.run(
            base + ["--control", "--json", ctl_json],
            capture_output=True, text=True, timeout=timeout / 3,
            env=ctl_env, cwd=root,
        )
    except subprocess.TimeoutExpired:
        return _fail(f"spmd control timed out after {timeout / 3:.0f}s")
    if proc.returncode != 0:
        return _fail("spmd control", proc)
    try:
        with open(ctl_json) as f:
            control = json.load(f)
    except (OSError, ValueError) as e:
        return _fail(f"control result unreadable: {e}")

    # 3) kill drill: SIGKILL stage 1 mid-run, expect exactly one
    # classified restart and a completed run
    drill_metrics = os.path.join(work, "drill.jsonl")
    drill_json = os.path.join(work, "drill.json")
    try:
        proc = subprocess.run(
            base + [
                "--workdir", os.path.join(work, "drill"),
                "--metrics_file", drill_metrics,
                "--chaos", "kill:stage1@step4",
                "--json", drill_json,
            ],
            capture_output=True, text=True, timeout=timeout / 3,
            env=env, cwd=root,
        )
    except subprocess.TimeoutExpired:
        return _fail(f"kill drill timed out after {timeout / 3:.0f}s")
    if proc.returncode != 0:
        return _fail("kill drill", proc)
    try:
        with open(drill_json) as f:
            drill = json.load(f)
    except (OSError, ValueError) as e:
        return _fail(f"drill result unreadable: {e}")

    # ---- aggregate ---------------------------------------------------
    records = _records(metrics_path)
    steps = [
        r for r in records
        if r.get("kind") == "step" and r.get("stage") is not None
    ]
    times = StatSummary()
    bubble = StatSummary()
    p2p_wait = StatSummary()
    for r in steps:
        wall = r.get("wall_s")
        if not wall:
            continue
        times.add(wall)
        if r.get("bubble_s") is not None:
            bubble.add(r["bubble_s"] / wall)
        if r.get("p2p_wait_s") is not None:
            p2p_wait.add(r["p2p_wait_s"] / wall)
    per_stage = {
        str(k): {
            "compile_s": round(float(f.get("compile_s", 0.0)), 3),
            "compiled_programs": f.get("compiled_programs"),
        }
        for k, f in (mpmd.get("final") or {}).items()
    }
    compile_sum = sum(
        v["compile_s"] for v in per_stage.values()
    )
    ctl_compile = float(control.get("compile_s") or 0.0)
    # THE subsystem claim: every stage compiled 1/K of the model, so
    # even summed across stages the compile bill undercuts the one
    # whole-model SPMD program.
    assert compile_sum < ctl_compile, (
        f"per-stage compiles sum to {compile_sum:.2f}s, not below the "
        f"SPMD single-program {ctl_compile:.2f}s"
    )
    mpmd_losses = []
    for r in sorted(
        (r for r in steps if r["stage"] == 0 and r.get("loss") is not None),
        key=lambda r: r["step"],
    ):
        mpmd_losses.append(float(r["loss"]))
    ctl_losses = [float(v) for v in control.get("losses") or []]
    loss_gap = (
        max(
            abs(a - b) for a, b in zip(mpmd_losses, ctl_losses)
        )
        if mpmd_losses and len(mpmd_losses) == len(ctl_losses)
        else None
    )
    # kill-drill recovery: fault (last step record before the restart
    # stamp) → first step record after it
    drill_recs = _records(drill_metrics)
    restart_recs = [
        r for r in drill_recs if r.get("kind") == "mpmd_restart"
    ]
    recovery = None
    if restart_recs:
        t_restart = float(restart_recs[0]["time"])
        pre = [
            float(r["time"]) for r in drill_recs
            if r.get("kind") == "step" and float(r["time"]) < t_restart
        ]
        post = [
            float(r["time"]) for r in drill_recs
            if r.get("kind") == "step" and float(r["time"]) >= t_restart
        ]
        if pre and post:
            recovery = min(post) - max(pre)
    ctl_steps = [float(s) for s in control.get("step_s") or []]
    ctl_summ = StatSummary()
    for s in ctl_steps[1:]:  # drop the compile-bearing first step
        ctl_summ.add(s)
    return {
        **provenance,
        "stages": mpmd.get("stages"),
        "steps": mpmd.get("steps"),
        "step_time_p50_s": round(times.percentile(50), 4)
        if times.count else None,
        "step_time_p99_s": round(times.percentile(99), 4)
        if times.count else None,
        "control_step_time_p50_s": round(ctl_summ.percentile(50), 4)
        if ctl_summ.count else None,
        "schedule_bubble_fraction": mpmd.get(
            "schedule_bubble_fraction"
        ),
        "measured_bubble_fraction": round(
            bubble.snapshot().get("mean", 0.0), 4
        )
        if bubble.count else None,
        "p2p_wait_fraction": round(
            p2p_wait.snapshot().get("mean", 0.0), 4
        )
        if p2p_wait.count else None,
        "per_stage_compile": per_stage,
        "compile_s_sum": round(compile_sum, 3),
        "control_compile_s": round(ctl_compile, 3),
        "control_compiled_programs": control.get("compiled_programs"),
        "loss_trajectory_max_gap": loss_gap,
        "loss_parity": bool(
            loss_gap is not None and loss_gap < 1e-3
        ),
        "kill_drill_restarts": drill.get("restarts"),
        "kill_drill_recovery_s": round(recovery, 3)
        if recovery is not None else None,
        "recovery_samples": 1 if recovery is not None else 0,
        "kill_drill_final_loss_gap": (
            abs(float(drill["loss"]) - float(mpmd["loss"]))
            if drill.get("loss") is not None
            and mpmd.get("loss") is not None
            else None
        ),
        "lint_clean": _lint_clean(),
    }


def run_zero_bench() -> dict:
    """Headline `zero` entry — in-process when the backend has ≥ 2
    devices, else re-run in a subprocess with 4 emulated CPU devices
    (world ≥ 2 is the point — nothing to scatter at 1 — and 4 lets
    the hierarchical variant emulate 2 slices × 2)."""
    import os
    import subprocess
    import sys

    import jax

    if len(jax.devices()) >= 2:
        return _zero_bench_impl()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--zero-worker"],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        return {"error": "zero worker timed out"}
    for line in reversed(proc.stdout.splitlines()):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if (
            isinstance(rec, dict)
            and rec.get("metric") == "zero_weight_update_sharding"
        ):
            rec["emulated_devices"] = True
            return rec
    return {
        "error": f"zero worker rc={proc.returncode}: "
        f"{proc.stderr[-800:]}"
    }


def run_accuracy_bench() -> dict:
    """North-star convergence proof on REAL handwritten-digit data.

    The one end-to-end claim the project is anchored on (BASELINE.md:
    ≥99% test accuracy within 3 MNIST epochs) had never been measured
    on real data — this environment has zero egress, so actual MNIST
    bytes are unreachable and every prior record degraded to the
    synthetic fallback. The real data used here: the UCI handwritten
    digits (sklearn's packaged ``load_digits`` scans — genuine digit
    raster data), vendored into MNIST's IDX container by
    ``scripts/vendor_uci_digits.py`` and committed under
    ``data/uci_digits/`` (1,437 train / 360 test, stratified).

    Two runs through the compiled per-step DDP path (the trainer CLI's
    step; NOT the scanned fast path — measured on this host, XLA:CPU
    compiles the conv step ~200× slower *inside* ``lax.scan`` than the
    identical step standalone, 3.4 s/step vs 15 ms/step, so the
    convergence proof uses the step path that is fast on both
    backends):

    - **reference recipe**: SGD lr=0.01, batch 32, 3 epochs, no
      augmentation — exactly ``/root/reference/train_ddp.py:41,218``
      transplanted onto the real vendored data;
    - **equal-sample budget**: 3 MNIST epochs = 180,000 samples seen;
      on 1,437 real examples that is 125 epochs. Adam + cosine decay +
      ±2px random-shift augmentation (data/augment.py) — the
      north-star ≥0.99 measured at MNIST's own sample budget, with
      the 3-epoch checkpoint of the same run reported alongside.

    Accuracy is evaluated on the untouched real test split; the
    augmentation never touches eval. Runs on whatever backend is up —
    convergence does not need the chip (round-3 verdict, missing #1).
    """
    import os
    import time

    import jax
    import jax.numpy as jnp
    import optax

    import numpy as np

    from ddp_tpu.data import mnist
    from ddp_tpu.data.augment import random_shift
    from ddp_tpu.models import get_model
    from ddp_tpu.parallel.ddp import (
        create_train_state,
        make_train_step,
        replicate_state,
    )
    from ddp_tpu.runtime.mesh import MeshSpec, make_mesh

    t_start = time.perf_counter()
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    train = mnist.load(root, "train", variant="uci_digits")
    test = mnist.load(root, "test", variant="uci_digits")
    n_train = int(train.images.shape[0])

    device = jax.devices()[0]
    mesh = make_mesh(MeshSpec(data=1), devices=[device])
    model = get_model("simple_cnn")
    batch = 32
    steps_per_epoch = n_train // batch
    test_x = jnp.asarray(test.images)
    test_y = jnp.asarray(test.labels)

    @jax.jit
    def test_accuracy(params):
        logits = model.apply(
            {"params": params}, test_x.astype(jnp.float32) / 255.0
        )
        return (jnp.argmax(logits, -1) == test_y).mean()

    def train_run(tx, epochs, augment_fn):
        state = replicate_state(
            create_train_state(
                model, tx, jnp.zeros((1, 28, 28, 1)), seed=0
            ),
            mesh,
        )
        step = make_train_step(
            model, tx, mesh, donate=False, seed=0, augment_fn=augment_fn,
        )
        images = jnp.asarray(train.images)
        labels = jnp.asarray(train.labels)
        rng = np.random.default_rng(0)
        acc_at_3 = None
        for e in range(epochs):
            perm = rng.permutation(n_train)
            for b in range(steps_per_epoch):
                sel = perm[b * batch : (b + 1) * batch]
                state, _ = step(state, images[sel], labels[sel])
            if e == 2:
                acc_at_3 = float(test_accuracy(state.params))
        return acc_at_3, float(test_accuracy(state.params))

    # Run 1 — the reference's own recipe on the real data.
    ref_acc3, _ = train_run(optax.sgd(0.01), 3, None)

    # Run 2 — the north star at MNIST's sample budget.
    budget_epochs = (3 * 60_000) // n_train  # = 125
    tuned_tx = optax.adam(
        optax.cosine_decay_schedule(
            1e-3, budget_epochs * steps_per_epoch, alpha=0.1
        )
    )
    tuned_acc3, budget_acc = train_run(tuned_tx, budget_epochs, random_shift)

    return {
        "real_data": True,
        **_env_fields(),
        "dataset": "uci_digits (sklearn load_digits scans, vendored "
                   "as IDX by scripts/vendor_uci_digits.py; real MNIST "
                   "unreachable — zero network egress)",
        "n_train": n_train,
        "n_test": int(test.images.shape[0]),
        "accuracy_3ep_reference_recipe": round(ref_acc3, 4),
        "accuracy_3ep_tuned": round(tuned_acc3, 4),
        "accuracy_mnist_equal_sample_budget": round(budget_acc, 4),
        "equal_budget_epochs": budget_epochs,
        "equal_budget_samples_seen": budget_epochs * steps_per_epoch * batch,
        "mnist_3ep_samples_seen": 180_000,
        "target": 0.99,
        "target_met_at_equal_budget": budget_acc >= 0.99,
        "seconds": round(time.perf_counter() - t_start, 1),
    }


def run_tune_bench() -> dict:
    """Autotuner entry (`python bench.py tune`, ISSUE 18): proves the
    cost model prunes, the search never regresses, and the cache makes
    the second run free.

    Cold pass on a fresh cache: the serve knob grid is enumerated,
    priced via the xprof compile ledger, dominated candidates dropped
    (``pruned_fraction > 0`` asserted — a cost model that prunes
    nothing is dead weight), survivors measured with the serve bench
    harness. ``tuned_p50 <= default_p50`` is asserted — the default
    config is always in the measured set and the winner is the p50
    argmin, so a tuner that can't beat the default returns it.

    Warm pass against the same cache file: asserted to be a pure hit —
    ``cache_hit`` true and ZERO measurements (the loaded-by-default
    path in trainer/serve/fleet costs nothing at startup).
    """
    import tempfile
    import time

    from ddp_tpu.models.lm import LMSpec, init_lm
    from ddp_tpu.tune import TuningCache, tune_serve, tune_zero

    env = _env_fields()
    spec = LMSpec(
        vocab_size=64, total_len=64, d_model=32, depth=1, num_heads=2
    )
    params = init_lm(spec, seed=0)
    with tempfile.TemporaryDirectory() as td:
        cache = TuningCache(os.path.join(td, "tuning_cache.json"))
        t0 = time.perf_counter()
        cold = tune_serve(
            spec, params, cache=cache, slots=2, max_measure=3
        )
        cold_wall = time.perf_counter() - t0
        assert not cold["cache_hit"], cold
        assert cold["pruned_fraction"] > 0, cold
        assert cold["measured"] >= 1, cold
        assert cold["tuned_p50"] <= cold["default_p50"], cold

        warm = tune_serve(
            spec, params, cache=cache, slots=2, max_measure=3
        )
        assert warm["cache_hit"] and warm["measured"] == 0, warm

        zero = tune_zero(
            params, 4, cache=cache, model_sig="bench", dcn=1
        )
        zero_warm = tune_zero(
            params, 4, cache=cache, model_sig="bench", dcn=1
        )
        assert zero_warm["cache_hit"] and zero_warm["measured"] == 0, (
            zero_warm
        )

    _assert_provenance(env)
    return {
        "metric": "autotune_search",
        **env,
        "proposed": cold["proposed"],
        "priced": cold["priced"],
        "pruned": cold["pruned"],
        "pruned_fraction": cold["pruned_fraction"],
        "cost_compiles": cold["cost_compiles"],
        "measured": cold["measured"],
        "measure_deferred": cold.get("measure_deferred", 0),
        "search_wall_s": round(cold_wall, 3),
        "default_p50_s": cold["default_p50"],
        "tuned_p50_s": cold["tuned_p50"],
        "winner": cold["winner"],
        "tuned_leq_default": True,
        "second_run_pure_cache_hit": True,
        "zero_winner": zero["winner"],
        "zero_pruned_fraction": zero["pruned_fraction"],
    }


def _run_extra_benches() -> None:
    """MXU-bound side benches → BENCH_EXTRA.json + stderr (TPU only)."""
    import pathlib
    import sys
    import traceback

    import jax

    out = pathlib.Path(__file__).with_name("BENCH_EXTRA.json")
    # Seed from the existing record so a partially-completed run
    # merges fresh entries over the old ones instead of erasing side
    # benches it never reached.
    extra = {}
    if out.exists():
        try:
            extra = json.loads(out.read_text())
        except (OSError, ValueError):
            extra = {}
    for name, fn in [
        ("vit", run_vit_bench),
        # Layout-tax experiment: T=64 (tile-aligned, mean-pool) vs the
        # T=65 cls-token run above — round-3 verdict weak #5.
        ("vit_t64", lambda: run_vit_bench(use_cls_token=False)),
        ("lm", run_lm_bench),
        ("lm_long", run_lm_long_bench),
        ("decode", run_decode_bench),
        ("decode_gqa", lambda: run_decode_bench(num_kv_heads=2)),
        # Round-5 MoE serving path: routed blocks through the same
        # KV-cache decode scan (GQA×MoE — the Mixtral-class config).
        ("decode_moe", lambda: run_decode_bench(
            num_kv_heads=2, num_experts=8)),
        # The serving data plane (ddp_tpu.serve): continuous-batching
        # engine under open-loop Poisson arrivals — sustained tokens/s
        # + TTFT, the complement of the raw decode scan above.
        ("serve_decode", run_serve_bench),
        # Shared-prefix serving (PR 12): paged KV + radix prefix
        # reuse — hit rate, effective-slots multiplier, TTFT hit vs
        # miss against a fixed-lane control on identical traffic.
        ("serve_prefix", run_serve_prefix_bench),
        # serve_fleet and serve_reload start whole server processes
        # as children, and this process holds the chip: a child that
        # needs it fails or hangs. They stay callable on their own
        # (``python bench.py serve_reload``; run_serve_fleet_bench).
        ("loader", run_loader_bench),
    ]:
        try:
            extra[name] = fn()
        except Exception:  # record, never break the headline bench
            extra[name] = {"error": traceback.format_exc(limit=3)}
        # Write after every entry: a kill mid-extras keeps whatever
        # completed instead of losing the whole file.
        out.write_text(json.dumps(extra, indent=2))
    print(json.dumps(extra), file=sys.stderr)


if __name__ == "__main__":
    import sys

    if "--zero-worker" in sys.argv:
        # Emulated-device measurement process for run_zero_bench
        # (spawned with 2 virtual CPU devices when the backend has
        # only one).
        print(json.dumps(_zero_bench_impl()), flush=True)
        sys.exit(0)
    if len(sys.argv) > 1 and sys.argv[1] == "serve_reload":
        # Model-lifecycle entry (ISSUE 20): in-flight-across-swap
        # completion, cold-vs-streaming TTFT, fleet swap vs /rollz
        # wall-clock — orderings asserted, one JSON line out.
        print(json.dumps(run_serve_reload_bench()), flush=True)
        sys.exit(0)
    if len(sys.argv) > 1 and sys.argv[1] == "tune":
        # Autotuner entry (ISSUE 18): pruned fraction, search
        # wall-clock, tuned-vs-default p50, cache-hit proof. One JSON
        # line, same contract as the headline.
        print(json.dumps(run_tune_bench()), flush=True)
        sys.exit(0)
    # One process: it holds the chip from here to the end. No retry,
    # no CPU fallback — run_bench refuses to start without a TPU, and
    # an exception ends the run with a non-zero exit. Headline line
    # FIRST so a crash in the heavier side benches cannot lose it.
    from ddp_tpu.runtime.dist import enable_compile_cache

    enable_compile_cache()
    result = run_bench()
    print(json.dumps(result), flush=True)
    # Side records, each merged into the headline and REPRINTED on
    # success (readers take the last parseable line), so a failure in
    # one never costs the headline: real-data convergence; ZeRO
    # weight-update sharding vs ddp at world ≥ 2 (ISSUE 7); the
    # elastic world-resize recovery drill (ISSUE 8) and the MPMD
    # pipeline runtime (ISSUE 17) — the last three on CPU children.
    import traceback

    for key, run in (
        ("real_data_accuracy", run_accuracy_bench),
        ("zero", run_zero_bench),
        ("elastic", run_elastic_bench),
        ("mpmd", run_mpmd_bench),
    ):
        try:
            result[key] = run()
            print(json.dumps(result), flush=True)
        except Exception:
            traceback.print_exc(file=sys.stderr)
    _run_extra_benches()
