#!/usr/bin/env python
"""Compile the Pallas kernels for the device JAX finds and compare each
with its reference.

    python scripts/check_kernels.py           # the shapes chip_smoke.py trains and serves
    python scripts/check_kernels.py --all     # + the variants the server exposes:
                                              #   GQA, int8 KV, paged lanes, and a
                                              #   block-diffusion MoE model's kernels
    python scripts/check_kernels.py --tiny    # small shapes (CPU rehearsal: the
                                              #   Pallas interpreter, not Mosaic)
    python scripts/check_kernels.py --all --only flash   # the cases so named
    python scripts/check_kernels.py --time    # device ms a call of flash_fwd and
                                              #   of the backward by form (resident:
                                              #   flash_dkv alone; grid: flash_dq +
                                              #   flash_dkv) at the train cells'
                                              #   shape, and of what XLA runs AROUND
                                              #   them; of flash_decode at the serve
                                              #   cells' (needs the chip)

On a TPU the kernels compile under Mosaic; elsewhere they run in the
Pallas interpreter, which proves the program and nothing about the
chip. References run under ``jax.default_matmul_precision("highest")``
on fp32 copies of the inputs. One JSON line per case —
``{"case", "kernel", "max_abs_err", "tol", "ok"}`` — then one summary
line; exit status 1 when any case is outside its tolerance or failed
to build. Only ``--time`` times anything: an attention layer's forward
and backward from the fused qkv projection to what ``proj`` reads, in a
profiler trace of a few calls — the flash training kernels by their
names (``ms_per_call``; ``backward_ms_per_call`` is ``flash_dq`` +
``flash_dkv``) and every other device operation of the call
(``around_ms_per_call``: the slices, transposes and stacks between
the projection and the kernels, 6.4 ms a step in the train cells until
PR 33) — with q, k, v sliced out for the separate-operand entry and
through the fused-projection entry, each with the backward in the form
the program chooses for the shape (``resident``, PR 39: one kernel over
a head held in VMEM) and held to the ``grid`` form (the loop a change
to ``ops/flash.py`` iterates in; no benchmark cell runs this). Then the
serve cells' ``flash_decode`` calls over rows with heads packed on lanes,
at the positions their lanes hold (``DECODE_CELLS``): ms a call, rows
attended and fetched, and the attended rows' bytes a second over the
HBM's (the loop a change to ``ops/decode.py``'s walk iterates in).
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Max |kernel − reference| accepted per case family: a few times what
# a TPU v5 lite showed (CHANGES.md, PR 21 — flash 7.5e-3 forward and
# 2.2e-2 backward, decode 8.5e-3 to 1.2e-2). Flash is bounded by
# bf16's 2^-8 rounding of its outputs. The decode kernel's fp32 dots
# run at the MXU's default precision (bf16 passes), like any fp32
# einsum XLA compiles for the chip without a precision request; each
# decode case also reports what the XLA reference itself loses at
# that default (``xla_default_max_abs_err``) for comparison.
TOL = {
    "flash": 2e-2,
    "flash_grad": 6e-2,
    "decode": 3e-2,
    # The grouped expert matmuls take bfloat16 operands (the stored
    # weights; the rows and the SiLU product rounded to match): the
    # error is relative to the output's largest magnitude, a few times
    # bfloat16's 2^-8.
    "moe_rel": 2e-2,
    # The state update is float32 elementwise arithmetic on the VPU and
    # one sum over 128 sublanes: float32 reassociation on values of
    # magnitude ~10.
    "ssm": 1e-4,
}


def _max_err(a, b) -> float:
    import jax.numpy as jnp

    return float(
        jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
    )


# The flash call of both train cells (benchmarks/configs/
# cerebras-gpt-1.3b-train.json): 4 rows of 2048 tokens a chip, 16 heads
# of 128, ``best_attention``'s default blocks.
CELL_FLASH = dict(B=4, T=2048, H=16, D=128, block=512)


def _flash_inputs(B, T, H, D, dtype):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.key(0), 4)
    q, k, v = (jax.random.normal(key, (B, T, H, D), dtype) for key in keys[:3])
    return q, k, v, jax.random.normal(keys[3], (B, T, H, D), jnp.float32)


@contextlib.contextmanager
def backward_form(form: str | None):
    """Hold ``ops/flash.py``'s backward to ``form`` (``grid`` or
    ``resident``) for what is TRACED inside: the program has no such
    switch, it chooses from the shapes (``flash._backward_form``), and
    at the cells' shape only this script and the tests run the other
    form. None: as the program chooses."""
    from ddp_tpu.ops import flash

    if form is None:
        yield
        return
    chosen = flash._backward_form
    flash._backward_form = lambda *a, **kw: (form, chosen(*a, **kw)[1])
    try:
        yield
    finally:
        flash._backward_form = chosen


def traced_forms(since: float) -> list:
    """The forms of the backward kernels traced after ``since`` (the
    tracer's clock, ``time.perf_counter()``: its ring is bounded), from
    their ``flash.plan`` records."""
    from ddp_tpu.obs.tracer import SPAN_NUMS, get_tracer

    form = SPAN_NUMS["flash.plan"].index("form")
    return sorted({e[4][form] for e in get_tracer().ring()
                   if e[0] == "flash.plan" and e[1] >= since
                   and e[4][0] != "flash_fwd"})


def check_flash(B: int, T: int, H: int, D: int, block: int,
                causal=True, dtype="bfloat16", backward=True,
                projection=False, form: str | None = None) -> dict:
    """Flash forward AND backward (the trainer's causal bf16 call)
    against dense attention. ``causal`` an int > 1: the block-causal
    mask of a block-diffusion prefill. ``backward=False``: the forward
    alone (the whole-prompt prefill's float32 call). ``projection``:
    through the fused entry, q, k, v as column blocks of one head-major
    [B, T, H·3·D] array (the train cells' call). ``form``: the backward
    held to it (:func:`backward_form`); the record says which ran."""
    import jax
    import jax.numpy as jnp

    from ddp_tpu.ops.attention import dot_product_attention
    from ddp_tpu.ops.flash import flash_attention, flash_attention_projection

    interpret = jax.default_backend() != "tpu"
    q, k, v, w = _flash_inputs(B, T, H, D, dtype)  # w: the cotangent

    def flash_loss(q, k, v):
        if projection:
            qkv = jnp.stack((q, k, v), axis=3).reshape(B, T, H * 3 * D)
            out = flash_attention_projection(
                qkv, H, causal, block, block, interpret).reshape(B, T, H, D)
        else:
            out = flash_attention(q, k, v, causal, block, block, interpret)
        return (out.astype(jnp.float32) * w).sum(), out

    block_causal = int(causal)  # 1: the plain triangle

    def dense_loss(q, k, v):
        out = dot_product_attention(q, k, v, causal=True, block=block_causal)
        return (out * w).sum(), out

    if not backward:
        out = jax.jit(lambda *a: flash_loss(*a)[1])(q, k, v)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda *a: dense_loss(*a)[1])(q, k, v)
        err = _max_err(out, ref)
        return {
            "max_abs_err": err, "tol": TOL["flash"],
            "ok": bool(jnp.isfinite(out).all()) and err <= TOL["flash"],
        }
    before = time.perf_counter()
    with backward_form(form):
        (_, out), grads = jax.jit(
            jax.value_and_grad(flash_loss, argnums=(0, 1, 2), has_aux=True)
        )(q, k, v)
    forms = traced_forms(before)
    with jax.default_matmul_precision("highest"):
        (_, ref), ref_grads = jax.jit(
            jax.value_and_grad(dense_loss, argnums=(0, 1, 2), has_aux=True)
        )(*(x.astype(jnp.float32) for x in (q, k, v)))
    err = _max_err(out, ref)
    grad_err = max(_max_err(g, r) for g, r in zip(grads, ref_grads))
    finite = bool(
        jnp.isfinite(out.astype(jnp.float32)).all()
        and all(jnp.isfinite(g.astype(jnp.float32)).all() for g in grads)
    )
    return {
        "max_abs_err": err,
        "tol": TOL["flash"],
        "grad_max_abs_err": grad_err,
        "grad_tol": TOL["flash_grad"],
        "backward_form": forms,
        "ok": finite and err <= TOL["flash"] and grad_err <= TOL["flash_grad"]
        and form in (None, *forms),
    }


def check_backward_forms(B: int, T: int, H: int, D: int, block: int) -> dict:
    """The two forms of the backward on the same numbers where the
    cells' call does not go: ``flash_attention_with_lse`` (a ring hop)
    with BOTH outputs differentiated, causal over half as many queries
    as keys. The resident form's dq, dk, dv against the grid form's
    (one pair function under both: the difference is the order the
    MXU's float32 sums run in) and against dense attention."""
    import jax
    import jax.numpy as jnp

    from ddp_tpu.ops.flash import _lse_rows, _reference, flash_attention_with_lse

    interpret = jax.default_backend() != "tpu"
    q, k, v, w = _flash_inputs(B, T, H, D, "bfloat16")
    q, w = q[:, T // 2:], w[:, T // 2:]
    u = jax.random.normal(jax.random.key(7), (B, T // 2, H), jnp.float32)

    def loss(attend):
        def f(q, k, v):
            out, lse = attend(q, k, v)
            return (out.astype(jnp.float32) * w).sum() + (lse * u).sum()
        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))

    def dense(q, k, v):
        scale = D ** -0.5
        s = jnp.einsum("bthd,bshd->bhts", q, k) * scale
        rows = jnp.arange(T // 2)[:, None] + T // 2
        s = jnp.where(rows >= jnp.arange(T)[None], s, -jnp.inf)
        lse = jax.nn.logsumexp(s, axis=-1)
        return _reference(q, k, v, True), lse.transpose(0, 2, 1)

    flash = lambda q, k, v: flash_attention_with_lse(
        q, k, v, True, block, block, interpret)
    grads = {}
    for form in ("resident", "grid"):
        with backward_form(form):
            grads[form] = loss(flash)(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = loss(dense)(*(x.astype(jnp.float32) for x in (q, k, v)))
    between = max(_max_err(a, b)
                  for a, b in zip(grads["resident"], grads["grid"]))
    err = max(_max_err(g, r) for g, r in zip(grads["resident"], ref))
    return {
        "max_abs_err": between, "tol": TOL["flash_grad"],
        "grad_max_abs_err": err, "grad_tol": TOL["flash_grad"],
        "ok": between <= TOL["flash_grad"] and err <= TOL["flash_grad"],
    }


FLASH_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def first_tpu_lines(log_dir: str):
    """The lines (``XLA Ops``, ``XLA Modules``, ...) of the first TPU's
    plane in the newest profiler trace under ``log_dir``; none where
    nothing ran on a chip."""
    import glob

    import jax

    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    planes = sorted(
        (p for p in jax.profiler.ProfileData.from_file(path).planes
         if p.name.startswith("/device:TPU:")), key=lambda p: p.name)
    return planes[0].lines if planes else ()


def device_ms_per_call(log_dir: str, calls: int,
                       kernels=FLASH_KERNELS) -> tuple[dict, float]:
    """Device ms a call in the profiler trace under ``log_dir``, from
    the ``XLA Ops`` events of the first TPU: of each of ``kernels`` (the
    events whose instruction carries the kernel's name,
    ``pallas_call(name=)``), and of every other operation together.
    ``({}, 0.0)`` where the trace holds no kernel event (nothing ran on
    a chip)."""
    import re

    total = dict.fromkeys(kernels, 0)
    named = re.compile(
        r"%?[\w\-]*?(" + "|".join(kernels) + r")(?![a-z])[\w\-.]* = ")
    around = 0
    for line in first_tpu_lines(log_dir):
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            # "%flash_fwd.3 = (bf16[...]) custom-call(...)"; under a
            # vjp the instruction is "%transpose_jvp_flash_dq__.1"
            m = named.match(ev.name)
            if m:
                total[m.group(1)] += ev.duration_ns
            else:
                around += ev.duration_ns
    if not any(total.values()):
        return {}, 0.0
    return ({k: ns / 1e6 / calls for k, ns in total.items()},
            around / 1e6 / calls)


TIME_ENTRIES = ("sliced", "projection")
TIME_FORMS = (None, "grid")  # as the program chooses; held to the grid


def time_flash(B: int, T: int, H: int, D: int, block: int,
               calls: int = 8, entry: str = "sliced",
               form: str | None = None) -> dict:
    """Device ms a call of one attention layer's forward and backward
    (causal, bf16) from the fused head-major projection [B, T, H·3·D]
    to [B, T, H·D] and back to the projection's cotangent: ``calls``
    calls inside a profiler session, after one that compiles.
    ``entry`` ``sliced``: q, k, v sliced out as ``models/vit.py`` does
    where the kernels cannot read the projection whole, the
    separate-operand entry, the cotangents stacked back; ``projection``:
    the fused entry. ``form``: the backward held to it
    (:func:`backward_form`). ``ms_per_call`` is the kernels' by name,
    ``backward_ms_per_call`` the backward's (``flash_dq`` +
    ``flash_dkv``: the resident form runs no ``flash_dq``),
    ``around_ms_per_call`` everything else the device ran."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from ddp_tpu.ops import flash

    interpret = jax.default_backend() != "tpu"
    kq, kg = jax.random.split(jax.random.key(0))
    qkv = jax.random.normal(kq, (B, T, H * 3 * D), jnp.bfloat16)
    g = jax.random.normal(kg, (B, T, H * D), jnp.bfloat16)

    def attend(qkv):
        if entry == "projection":
            return flash.flash_attention_projection(
                qkv, H, True, block, block, interpret)
        x = qkv.reshape(B, T, H, 3, D)
        return flash.flash_attention(
            x[:, :, :, 0], x[:, :, :, 1], x[:, :, :, 2], True, block, block,
            interpret,
        ).reshape(B, T, H * D)

    @jax.jit
    def fwd_bwd(qkv, g):
        out, vjp = jax.vjp(attend, qkv)
        return out, vjp(g)

    before = time.perf_counter()
    with backward_form(form):
        jax.block_until_ready(fwd_bwd(qkv, g))
    forms = traced_forms(before)
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir)
        try:
            for _ in range(calls):
                res = fwd_bwd(qkv, g)
            jax.block_until_ready(res)
        finally:
            jax.profiler.stop_trace()
        ms, around = device_ms_per_call(log_dir, calls)
    return {
        "shape": dict(B=B, T=T, H=H, D=D, block=block), "calls": calls,
        "backward_form": forms, "ms_per_call": ms, "ok": bool(ms),
        **({"backward_ms_per_call": ms["flash_dq"] + ms["flash_dkv"],
            "around_ms_per_call": around} if ms
           else {"error": "no TPU in the trace: nothing timed"}),
    }


# HBM bytes a second of the chip ``--time`` is read on (TPU v5e; Google
# Cloud's "TPU v5e" page): a decode call's share of it is the bytes of
# the rows it ATTENDS (K and V, once) over its device time.
HBM_BYTES_PER_S = 819e9


def lane_positions(S: int, L: int, mean_rows: int):
    """``S`` lane positions as a serve cell past its knee holds them:
    spread evenly from a quarter to seven quarters of ``mean_rows`` rows
    and dealt so that neighbours differ; ``mean_rows`` at ``L`` or above
    is a ring that has wrapped (every lane at its last row)."""
    import numpy as np

    if mean_rows >= L:
        return np.full(S, L - 1, np.int32)
    rows = np.linspace(mean_rows / 4, 7 * mean_rows / 4, S).round()
    return (np.random.default_rng(0).permutation(rows) - 1).astype(np.int32)


def _decode_call(kind: str, S: int, L: int, depth: int, mean_rows: int):
    """The serve cells' ``flash_decode`` calls at their widths: ``diff``
    the SambaY decoder's (20 head pairs over 10 kv pairs, rows of 1,280),
    ``packed`` the hybrid's (4 queries a kv head of 64 at scale 1/64,
    rows of 512), the last of ``depth`` stored layers -> (a function of
    ``impl``, the positions, the row width)."""
    import jax
    import jax.numpy as jnp

    from ddp_tpu.ops import decode

    H, H_kv, Dh = (40, 20, 64) if kind == "diff" else (32, 8, 64)
    kq, kk, kv = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(kq, (S, H, Dh), jnp.float32)
    k = jax.random.normal(kk, (depth, S, L, H_kv * Dh), jnp.float32)
    v = jax.random.normal(kv, (depth, S, L, H_kv * Dh), jnp.float32)
    pos = lane_positions(S, L, mean_rows)
    attend = (decode.diff_decode_attention if kind == "diff"
              else functools.partial(decode.packed_decode_attention,
                                     scale=1 / 64))
    fn = lambda impl: jax.jit(lambda q, k, v, p: attend(
        q, k, v, p, layer=depth - 1, impl=impl))(q, k, v, jnp.asarray(pos))
    return fn, pos, H_kv * Dh


def _rows(pos, L: int) -> dict:
    from ddp_tpu.ops.decode import fetched_rows

    return {"rows_attended": int((pos + 1).sum()),
            "rows_fetched": int(fetched_rows(pos, L).sum())}


def check_decode_cell(kind: str, S: int, L: int, depth: int,
                      mean_rows: int) -> dict:
    """A serve cell's ``flash_decode`` call (:func:`_decode_call`) at the
    positions its lanes hold against the reference."""
    import jax
    import jax.numpy as jnp

    call, pos, _ = _decode_call(kind, S, L, depth, mean_rows)
    out = call("flash")
    with jax.default_matmul_precision("highest"):
        err = _max_err(out, call("reference"))
    return {
        "max_abs_err": err, "tol": TOL["decode"], **_rows(pos, L),
        "ok": bool(jnp.isfinite(out).all()) and err <= TOL["decode"],
    }


def time_decode(kind: str, S: int, L: int, depth: int, mean_rows: int,
                calls: int = 8) -> dict:
    """Device ms a call of a serve cell's ``flash_decode``
    (:func:`_decode_call`): ``calls`` calls inside a profiler session,
    after one that compiles; the rows it attends and fetches, and the
    attended rows' bytes (K and V) a second over the HBM's."""
    import tempfile

    import jax

    call, pos, W = _decode_call(kind, S, L, depth, mean_rows)
    jax.block_until_ready(call("flash"))
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir)
        try:
            for _ in range(calls):
                out = call("flash")
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        ms, _ = device_ms_per_call(log_dir, calls, ("flash_decode",))
    rows = _rows(pos, L)
    rec = {"shape": dict(S=S, L=L, W=W, depth=depth, mean_rows=mean_rows),
           "calls": calls, "ms_per_call": ms, **rows, "ok": bool(ms)}
    if not ms:
        return {**rec, "error": "no TPU in the trace: nothing timed"}
    needed = rows["rows_attended"] * W * 4 * 2
    return {**rec, "hbm_share_pct": round(
        100 * needed / HBM_BYTES_PER_S / (ms["flash_decode"] / 1e3), 2)}


# The serve cells' flash_decode calls (PERF.md section 5): the SambaY
# cell's shared rows at a mean of 1,350 of 4,096 and its wrapped rings,
# the hybrid cell's rows at ~320 of 2,048; 64 lanes each.
DECODE_CELLS = {
    "diff_shared": dict(kind="diff", S=64, L=4096, depth=1, mean_rows=1350),
    "diff_ring": dict(kind="diff", S=64, L=512, depth=8, mean_rows=512),
    "packed": dict(kind="packed", S=64, L=2048, depth=4, mean_rows=320),
}
DECODE_CELLS_TINY = {
    "diff_shared": dict(kind="diff", S=3, L=512, depth=1, mean_rows=170),
    "diff_ring": dict(kind="diff", S=3, L=128, depth=2, mean_rows=128),
    "packed": dict(kind="packed", S=3, L=256, depth=2, mean_rows=40),
}


def check_decode(
    S: int, H: int, H_kv: int, Dh: int, L: int,
    *, int8: bool = False, page_size: int = 0, depth: int = 0,
) -> dict:
    """Flash-decode (fixed-lane, or paged through a shuffled page
    table) against ``decode_attention_reference`` on the same cache.
    ``depth`` > 0 hands the kernel what the serve engine's decode step
    does: the stored ``[depth, S, L, H_kv, Dh]`` cache and a layer
    index (the last layer; the others hold other numbers)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddp_tpu.ops.decode import (
        decode_attention,
        decode_attention_reference,
        gather_paged_kv,
        paged_decode_attention,
        quantize_kv,
    )

    kq, kk, kv = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(kq, (S, H, Dh), jnp.float32)
    k = jax.random.normal(kk, (S, L, H_kv, Dh), jnp.float32)
    v = jax.random.normal(kv, (S, L, H_kv, Dh), jnp.float32)
    # Every lane age the band must handle: the first key alone, a
    # block edge either side, the full lane.
    pos = jnp.asarray(
        np.resize([0, 127, 128, L // 2, L - 1, 1, L // 3, L - 2], S),
        jnp.int32,
    ) % L
    ks = vs = None
    if int8:
        k, ks = quantize_kv(k)
        v, vs = quantize_kv(v)
    if depth:
        layer = depth - 1

        def stored(x):
            return jnp.stack([x[::-1]] * layer + [x])

        cache = [stored(x) if x is not None else None for x in (k, v, ks, vs)]
        out = jax.jit(
            lambda q, k, v, ks, vs: decode_attention(
                q, k, v, pos, ks, vs, impl="flash", layer=layer
            )
        )(q, *cache)
    elif page_size:
        # Scatter every lane's pages over a shuffled pool (page 0 is
        # the engine's scratch page and stays unmapped).
        n = L // page_size
        table = jnp.asarray(
            1 + np.random.default_rng(2).permutation(S * n).reshape(S, n),
            jnp.int32,
        )

        def to_pool(x):
            pages = x.reshape(S * n, page_size, *x.shape[2:])
            pool = jnp.zeros((S * n + 1, *pages.shape[1:]), x.dtype)
            return pool.at[table.reshape(-1)].set(pages)

        pools = [to_pool(x) if x is not None else None for x in (k, v, ks, vs)]
        out = jax.jit(
            lambda q, kp, vp, ksp, vsp: paged_decode_attention(
                q, kp, vp, table, pos, ksp, vsp, impl="flash"
            )
        )(q, *pools)
        k, v, ks, vs = (
            gather_paged_kv(x, table) if x is not None else None
            for x in pools
        )
    else:
        out = jax.jit(
            lambda q, k, v, ks, vs: decode_attention(
                q, k, v, pos, ks, vs, impl="flash"
            )
        )(q, k, v, ks, vs)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(decode_attention_reference)(q, k, v, pos, ks, vs)
    xla_default = jax.jit(decode_attention_reference)(q, k, v, pos, ks, vs)
    err = _max_err(out, ref)
    return {
        "max_abs_err": err,
        "tol": TOL["decode"],
        "xla_default_max_abs_err": _max_err(xla_default, ref),
        "ok": bool(jnp.isfinite(out).all()) and err <= TOL["decode"],
    }


def check_moe(N: int, d: int, f: int, E: int, top_k: int) -> dict:
    """The sort-by-expert grouped matmuls (ops/moe.py: gate and up,
    then down, no drops) against every expert run on every token in
    fp32, on bfloat16 weights as a served model stores them."""
    import jax
    import jax.numpy as jnp

    from ddp_tpu.ops.moe import moe_layer, moe_reference

    ks = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(ks[0], (N, d), jnp.float32)
    logits = jax.random.normal(ks[1], (N, E), jnp.float32)
    wg, wu, wd = (
        (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(jnp.bfloat16)
        for k, shape in zip(ks[2:], ((E, d, f), (E, d, f), (E, f, d)))
    )
    out, stats = jax.jit(
        lambda *a: moe_layer(*a, top_k=top_k, impl="pallas")
    )(x, logits, wg, wu, wd)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(
            lambda *a: moe_reference(*a, top_k=top_k)
        )(x, logits, wg, wu, wd)
    err = _max_err(out, ref)
    rel = err / float(jnp.abs(ref).max())
    return {
        "max_abs_err": err, "rel_err": rel, "tol": TOL["moe_rel"],
        "rows_routed": int(stats[0]), "fullest_expert": int(stats[1]),
        "experts_hit": int(stats[2]),
        "ok": bool(jnp.isfinite(out).all()) and rel <= TOL["moe_rel"],
    }


def check_moe_share(N: int, d: int, f: int, E: int, held: int, first: int,
                    top_k: int) -> dict:
    """One member's part of an expert layer divided over several
    (ops/moe.moe_share_layer: sigmoid scores, a choice bias, ``held`` of
    the ``E`` experts held here from number ``first``), the gate/up
    kernel a column block of the expert width at a time where the
    matrices are too wide for VMEM, against every held expert run on
    every token in fp32 and weighted by the routed weights."""
    import jax
    import jax.numpy as jnp

    from ddp_tpu.ops.moe import column_block, moe_share_layer, route

    ks = jax.random.split(jax.random.key(5), 6)
    x = jax.random.normal(ks[0], (N, d), jnp.float32)
    logits = jax.random.normal(ks[1], (N, E), jnp.float32)
    bias = 0.3 * jax.random.normal(ks[5], (E,), jnp.float32)
    wg, wu, wd = (
        (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(jnp.bfloat16)
        for k, shape in zip(ks[2:5], ((held, d, f), (held, d, f),
                                      (held, f, d)))
    )
    kw = dict(top_k=top_k, scoring="sigmoid", bias=bias, scale=2.5)
    out, stats = jax.jit(lambda *a: moe_share_layer(
        *a, first=first, impl="pallas", **kw))(x, logits, wg, wu, wd)

    def plain(x, logits, wg, wu, wd):
        idx, w = route(logits, top_k, True, scoring="sigmoid", bias=bias,
                       scale=2.5)
        comb = jnp.zeros(logits.shape, jnp.float32).at[
            jnp.arange(N)[:, None], idx].add(w)[:, first:first + held]
        f32 = lambda a: a.astype(jnp.float32)
        h = jax.nn.silu(jnp.einsum("nd,edf->enf", x, f32(wg))) * jnp.einsum(
            "nd,edf->enf", x, f32(wu))
        return jnp.einsum("ne,end->nd", comb,
                          jnp.einsum("enf,efd->end", h, f32(wd)))

    with jax.default_matmul_precision("highest"):
        ref = jax.jit(plain)(x, logits, wg, wu, wd)
    err = _max_err(out, ref)
    rel = err / float(jnp.abs(ref).max())
    return {
        "max_abs_err": err, "rel_err": rel, "tol": TOL["moe_rel"],
        "pairs_routed": int(stats[0]), "pairs_held": int(stats[1]),
        "experts_hit": int(stats[3]),
        "column_block": column_block(d, f, 2),
        "ok": bool(jnp.isfinite(out).all()) and rel <= TOL["moe_rel"]
        and 0 < int(stats[1]) < int(stats[0]),
    }


def check_latent_decode(S: int, H: int, R: int, Dr: int, Hi: int, Di: int,
                        L: int, top_k: int) -> dict:
    """A decode step over a latent cache whose keys are selected
    (ops/decode.py: index scores, the top-k, the gathered rows under
    absorbed attention; plain XLA) against the same mathematics in fp32
    under an explicit mask, lanes at positions from a few rows to the
    lane's end."""
    import jax
    import jax.numpy as jnp

    from ddp_tpu.ops import decode as dec

    ks = jax.random.split(jax.random.key(9), 5)
    ki = jax.random.normal(ks[0], (S, L, Di), jnp.bfloat16)
    lat = jax.random.normal(ks[1], (S, L, R + Dr), jnp.bfloat16)
    qi = jax.random.normal(ks[2], (S, Hi, Di), jnp.float32)
    w = jax.random.normal(ks[3], (S, Hi), jnp.float32)
    q = jax.random.normal(ks[4], (S, H, R + Dr), jnp.float32)
    pos = jnp.asarray([(L - 1) * (s + 1) // S for s in range(S)], jnp.int32)
    pos = pos.at[0].set(min(5, L - 1))
    scale = (R + Dr) ** -0.5

    @jax.jit
    def program(qi, w, ki, q, lat, pos):
        rows, counted = dec.select_rows(dec.index_scores(qi, w, ki), pos,
                                        top_k)
        return dec.latent_decode_attention(q, lat, rows, counted, rank=R,
                                           scale=scale), rows, counted

    def plain(qi, w, ki, q, lat, pos):
        f32 = lambda a: a.astype(jnp.float32)
        qb = f32(qi.astype(jnp.bfloat16))
        I = jnp.einsum("shl,sh->sl", jax.nn.relu(
            jnp.einsum("shd,sld->shl", qb, f32(ki))), w)
        live = jnp.arange(L)[None, :] <= pos[:, None]
        I = jnp.where(live, I, -jnp.inf)
        best = jax.lax.top_k(I, min(top_k, L))[1]
        sel = live & jnp.zeros((S, L), bool).at[
            jnp.arange(S)[:, None], best].set(True)
        s = jnp.einsum("shw,slw->shl", f32(q.astype(jnp.bfloat16)),
                       f32(lat)) * scale
        p = jax.nn.softmax(jnp.where(sel[:, None], s, -jnp.inf), -1)
        return jnp.einsum("shl,slr->shr", p, f32(lat)[..., :R]), sel

    out, rows, counted = program(qi, w, ki, q, lat, pos)
    with jax.default_matmul_precision("highest"):
        ref, sel = jax.jit(plain)(qi, w, ki, q, lat, pos)
    mine = jnp.zeros((S, L), bool).at[
        jnp.arange(S)[:, None], rows].max(counted)
    err = _max_err(out, ref)
    return {
        "max_abs_err": err, "tol": TOL["decode"],
        "rows_selected": [int(c) for c in counted.sum(-1)],
        "rows_that_differ": int((mine != sel).sum()),
        # bf16 probabilities into the value product: the flash kernels'
        # contract, not float32's
        "ok": bool(jnp.isfinite(out).all()) and err <= 2e-2
        and int((mine != sel).sum()) <= S,
    }


# A GLM-5 prefill chunk's attention (PERF.md section 5): 2,048 queries
# of 64 heads deep in a lane of 17,408 rows stored 640 wide, 14 blocks of
# 512 keys live, 2,048 keys a query selected by 32 index heads of 128.
LATENT_PREFILL = dict(C=2048, H=64, R=512, Dn=192, Dr=64, Dv=256, W=640,
                      Hi=32, Di=128, S=2, L=17408, top_k=2048,
                      live_blocks=14, key_block=512, query_tile=1024)
LATENT_PREFILL_TINY = dict(C=64, H=4, R=16, Dn=8, Dr=8, Dv=8, W=128, Hi=2,
                           Di=16, S=2, L=128, top_k=8, live_blocks=6,
                           key_block=16, query_tile=32)


def _latent_prefill_call(C, H, R, Dn, Dr, Dv, W, Hi, Di, S, L, top_k,
                         live_blocks, key_block, query_tile):
    """``glm_dsa.chunk_attention`` on the last lane of a stored buffer,
    the chunk's queries the last ``C`` positions of the live blocks, the
    rows above them NaN (never read) -> a function of ``impl`` that
    returns (output ``[C, H * Dv]``, mask ``[C, L]``)."""
    import jax
    import jax.numpy as jnp

    from ddp_tpu.models import glm_dsa as gd
    from ddp_tpu.models.lm import LMSpec

    spec = LMSpec(
        vocab_size=8, total_len=L, num_heads=H, block="glm_dsa",
        kv_lora_rank=R, qk_nope_head_dim=Dn, qk_rope_head_dim=Dr,
        v_head_dim=Dv, index_n_heads=Hi, index_head_dim=Di, index_topk=top_k)
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.key(11), 7)
    live = live_blocks * key_block
    rows = jnp.arange(L)[None, :, None]
    stored = lambda key, width, real: jnp.where(
        rows < live,
        jnp.pad(jax.random.normal(key, (S, L, real), bf),
                ((0, 0), (0, 0), (0, width - real))), jnp.nan)
    latent, index_k = stored(ks[0], W, R + Dr), stored(ks[1], Di, Di)
    p = {"kv_b_proj": (jax.random.normal(ks[2], (R, H * (Dn + Dv)))
                       * R ** -0.5).astype(bf)}
    q_nope = jax.random.normal(ks[3], (C, H, Dn))
    q_rope = jax.random.normal(ks[4], (C, H, Dr))
    qi = jax.random.normal(ks[5], (C, Hi, Di))
    w = jax.random.normal(ks[6], (C, Hi))
    q_pos = live - C + jnp.arange(C, dtype=jnp.int32)

    def call(impl):
        old = gd.KEY_BLOCK, gd.QUERY_TILE
        gd.KEY_BLOCK, gd.QUERY_TILE = key_block, query_tile
        try:
            return jax.jit(lambda *a: gd.chunk_attention(
                spec, p, *a, want_mask=True, impl=impl))(
                    q_nope, q_rope, qi, w, latent, index_k, S - 1,
                    jnp.asarray(live_blocks, jnp.int32), q_pos)
        finally:
            gd.KEY_BLOCK, gd.QUERY_TILE = old

    return call


def check_latent_prefill(**shape) -> dict:
    """The ``latent_prefill`` kernel inside a chunk's three passes
    against the ``jnp`` walk of the same passes: the same operands, the
    same selection (the masks must be equal), the online softmax's
    sums in another order."""
    import jax.numpy as jnp

    call = _latent_prefill_call(**shape)
    out, mask = call("pallas")
    ref, ref_mask = call("jnp")
    err = _max_err(out, ref)
    differ = int((mask != ref_mask).sum())
    return {
        "max_abs_err": err, "tol": TOL["decode"], "masks_differ": differ,
        "pairs_selected": int(mask.sum()),
        "ok": bool(jnp.isfinite(out).all()) and err <= TOL["decode"]
        and differ == 0,
    }


def time_latent_prefill(calls: int = 4, forms=("pallas", "jnp"),
                        **shape) -> dict:
    """Device ms a call of a chunk's three passes in both forms
    (``calls`` executions inside a profiler session, after one that
    compiles): the whole program's (the ``XLA Modules`` line), and of it
    the ``latent_prefill`` kernel's, and the eight dearest operations by
    instruction name. The forms share passes one and two, so the
    programs' difference is the third pass's."""
    import tempfile

    import jax

    call = _latent_prefill_call(**shape)
    rec = {"shape": shape, "calls": calls}
    for impl in forms:
        jax.block_until_ready(call(impl))
        with tempfile.TemporaryDirectory() as log_dir:
            jax.profiler.start_trace(log_dir)
            try:
                for _ in range(calls):
                    out = call(impl)
                jax.block_until_ready(out)
            finally:
                jax.profiler.stop_trace()
            kernel, _ = device_ms_per_call(log_dir, calls, ("latent_prefill",))
            program, ops = 0, {}
            for line in first_tpu_lines(log_dir):
                for ev in line.events:
                    if line.name == "XLA Modules":
                        program += ev.duration_ns
                    elif line.name == "XLA Ops":
                        # "%fusion.12 = ..." -> "fusion"; a loop's body
                        # is there beside the loop
                        op = ev.name.split(" = ")[0].lstrip("%").split(".")[0]
                        if op not in ("while", "conditional", "call"):
                            ops[op] = ops.get(op, 0) + ev.duration_ns
        rec[f"{impl}_program_ms_per_call"] = program / 1e6 / calls
        rec[f"{impl}_kernel_ms_per_call"] = kernel.get("latent_prefill", 0.0)
        rec[f"{impl}_ops_ms_per_call"] = {
            op: round(ns / 1e6 / calls, 3) for op, ns in sorted(
                ops.items(), key=lambda kv: -kv[1])[:8]}
    rec["ok"] = bool(rec["pallas_kernel_ms_per_call"])
    if not rec["ok"]:
        rec["error"] = "no TPU in the trace: nothing timed"
    return rec


def check_decode_packed(S: int, H: int, H_kv: int, Dh: int, L: int,
                        depth: int, scale: float) -> dict:
    """Flash-decode over rows stored with their kv heads side by side
    on lanes (``[depth, S, L, H_kv * Dh]``, a head size under 128) and
    a softmax scale that is not ``Dh ** -0.5``, against the reference
    on the same rows viewed ``[S, L, H_kv, Dh]``; the last layer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddp_tpu.ops.decode import packed_decode_attention

    kq, kk, kv = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(kq, (S, H, Dh), jnp.float32)
    k = jax.random.normal(kk, (depth, S, L, H_kv * Dh), jnp.float32)
    v = jax.random.normal(kv, (depth, S, L, H_kv * Dh), jnp.float32)
    pos = jnp.asarray(
        np.resize([0, 127, 128, L // 2, L - 1, 1, L // 3, L - 2], S),
        jnp.int32,
    ) % L

    def call(impl):
        return jax.jit(lambda q, k, v: packed_decode_attention(
            q, k, v, pos, layer=depth - 1, impl=impl, scale=scale))(q, k, v)

    out = call("flash")
    with jax.default_matmul_precision("highest"):
        ref = call("reference")
    err = _max_err(out, ref)
    return {
        "max_abs_err": err,
        "tol": TOL["decode"],
        "xla_default_max_abs_err": _max_err(call("reference"), ref),
        "ok": bool(jnp.isfinite(out).all()) and err <= TOL["decode"],
    }


def check_ssm_update(S: int, H: int, P: int, N: int, layers: int,
                     live_every: int) -> dict:
    """``ssm_state_update`` against ``jnp`` on the stored ``[layers, S,
    N, H * P]`` state, the middle layer, with every ``live_every``-th
    lane idle: live lanes to float32 rounding, idle lanes and the other
    layers bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddp_tpu.ops import ssm

    k = jax.random.split(jax.random.key(3), 7)
    state = jax.random.normal(k[0], (layers, S, N, H * P), jnp.float32)
    args = (jax.random.normal(k[1], (S, H, P)),
            jax.nn.softplus(jax.random.normal(k[2], (S, H)) - 2.0),
            -jnp.exp(jax.random.uniform(k[3], (H,), minval=0.0, maxval=2.7)),
            jax.random.normal(k[4], (S, N)), jax.random.normal(k[5], (S, N)),
            jnp.ones((H,)))
    live = jnp.asarray(np.arange(S) % live_every != live_every - 1)
    layer = layers // 2

    def call(impl):
        return jax.jit(lambda s, *a: ssm.ssm_state_update(
            s, layer, *a, live, impl=impl))(state, *args)

    got_s, got_y = call("pallas")
    want_s, want_y = call("jnp")
    idle = ~np.asarray(live)
    untouched = (
        bool(jnp.array_equal(got_s[layer][idle], state[layer][idle]))
        and bool(jnp.array_equal(got_s[:layer], state[:layer]))
        and bool(jnp.array_equal(got_s[layer + 1:], state[layer + 1:]))
    )
    err = max(_max_err(got_s, want_s), _max_err(got_y, want_y))
    return {
        "max_abs_err": err, "tol": TOL["ssm"],
        "idle_lanes_and_other_layers_bit_equal": untouched,
        "live_lanes": int(live.sum()),
        "ok": bool(jnp.isfinite(got_y).all()) and untouched
        and err <= TOL["ssm"],
    }


def check_decode_diff(S: int, H: int, H_kv: int, L: int, depth: int,
                      ring: bool) -> dict:
    """The two softmax maps of every head pair (differential
    attention, heads of 64) through ``flash_decode`` on rows stored
    ``[depth, S, L, H_kv * 64]`` against the reference that forms each
    map separately; the last layer. ``ring``: ``L`` is a window's ring
    and the position passed is the count of valid rows less one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddp_tpu.ops.decode import diff_decode_attention

    Dh = 64
    kq, kk, kv = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(kq, (S, H, Dh), jnp.float32)
    k = jax.random.normal(kk, (depth, S, L, H_kv * Dh), jnp.float32)
    v = jax.random.normal(kv, (depth, S, L, H_kv * Dh), jnp.float32)
    pos = np.resize([0, 127, 128, L // 2, L - 1, 1, 5 * L // 3, 3 * L], S)
    pos = jnp.asarray(np.minimum(pos, L - 1) if ring else pos % L, jnp.int32)

    def call(impl):
        return jax.jit(lambda q, k, v: diff_decode_attention(
            q, k, v, pos, layer=depth - 1, impl=impl))(q, k, v)

    out = call("flash")
    with jax.default_matmul_precision("highest"):
        ref = call("reference")
    err = _max_err(out, ref)
    return {
        "max_abs_err": err, "tol": TOL["decode"],
        "ok": bool(jnp.isfinite(out).all()) and err <= TOL["decode"],
    }


def check_selective_update(S: int, C: int, N: int, layers: int,
                           live_every: int) -> dict:
    """``selective_state_update`` (a decay for every state index and
    channel, formed in the kernel) against ``jnp`` on the stored
    ``[layers, S, N, C]`` state, the middle layer, with every
    ``live_every``-th lane idle: live lanes to float32 rounding, idle
    lanes and the other layers bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddp_tpu.ops import ssm

    k = jax.random.split(jax.random.key(7), 6)
    state = jax.random.normal(k[0], (layers, S, N, C), jnp.float32)
    args = (jax.random.normal(k[1], (S, C)),
            jax.nn.softplus(jax.random.normal(k[2], (S, C)) - 2.0),
            -jnp.broadcast_to(jnp.arange(1.0, N + 1)[:, None], (N, C)),
            jax.random.normal(k[3], (S, N)), jax.random.normal(k[4], (S, N)),
            jnp.ones((C,)))
    live = jnp.asarray(np.arange(S) % live_every != live_every - 1)
    layer = layers // 2

    def call(impl):
        return jax.jit(lambda s, *a: ssm.selective_state_update(
            s, layer, *a, live, impl=impl))(state, *args)

    got_s, got_y = call("pallas")
    want_s, want_y = call("jnp")
    idle = ~np.asarray(live)
    untouched = (
        bool(jnp.array_equal(got_s[layer][idle], state[layer][idle]))
        and bool(jnp.array_equal(got_s[:layer], state[:layer]))
        and bool(jnp.array_equal(got_s[layer + 1:], state[layer + 1:]))
    )
    err = max(_max_err(got_s, want_s), _max_err(got_y, want_y))
    return {
        "max_abs_err": err, "tol": TOL["ssm"],
        "idle_lanes_and_other_layers_bit_equal": untouched,
        "live_lanes": int(live.sum()),
        "ok": bool(jnp.isfinite(got_y).all()) and untouched
        and err <= TOL["ssm"],
    }


def check_selective_scan(T: int, C: int, N: int, real: int) -> dict:
    """``selective_scan`` over ``T`` positions of which ``real`` are
    real (``dt`` 0 after them) from a non-zero carried state, against
    the sequential ``lax.scan``; and the device ms a call."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from ddp_tpu.ops import ssm

    k = jax.random.split(jax.random.key(9), 6)
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, C)) - 2.0)
    args = (jax.random.normal(k[0], (T, C)),
            jnp.where((jnp.arange(T) < real)[:, None], dt, 0.0),
            -jnp.broadcast_to(jnp.arange(1.0, N + 1)[:, None], (N, C)),
            jax.random.normal(k[2], (T, N)), jax.random.normal(k[3], (T, N)),
            jax.random.normal(k[4], (N, C)))

    def call(impl):
        return jax.jit(lambda *a: ssm.selective_scan(*a, impl=impl))

    fn = call("pallas")
    got_y, got_s = jax.block_until_ready(fn(*args))
    t = _time.perf_counter()
    for _ in range(10):
        out = fn(*args)
    jax.block_until_ready(out)
    wall_ms = (_time.perf_counter() - t) * 100.0
    want_y, want_s = call("jnp")(*args)
    # 512 dependent steps of a sum of 16 products: rounding accumulates
    tol = 10 * TOL["ssm"]
    err = max(_max_err(got_s, want_s), _max_err(got_y[:real], want_y[:real]))
    on_chip = jax.default_backend() == "tpu"  # a CPU's time is no reading
    return {"max_abs_err": err, "tol": tol,
            "wall_ms_per_call": wall_ms if on_chip else None,
            "ok": bool(jnp.isfinite(got_y).all()) and err <= tol}


def cases(tiny: bool, every: bool):
    if tiny:
        flash = dict(B=1, T=128, H=2, D=128, block=64)
        dec = dict(S=2, H=4, H_kv=4, Dh=128, L=256)
    else:
        # chip_smoke.py's model: d1024 / 8 heads, T=2048, 8 slots.
        flash = dict(B=2, T=2048, H=8, D=128, block=512)
        dec = dict(S=8, H=8, H_kv=8, Dh=128, L=2048)
    yield "flash_fwd_bwd_bf16_causal", lambda: check_flash(**flash)
    yield "decode_fp32_g1", lambda: check_decode(**dec)
    # The path a serving cell runs: the stored cache of the benchmark's
    # model (16 heads of 128, 8 lanes of 2048), a layer other than 0.
    stored = dict(dec, depth=2) if tiny else dict(dec, H=16, H_kv=16, depth=3)
    yield "decode_fp32_g1_stored", lambda: check_decode(**stored)
    if every:
        gqa = dict(dec, H_kv=dec["H"] // 4)
        yield "decode_fp32_g4", lambda: check_decode(**gqa)
        yield "decode_fp32_g8", lambda: check_decode(
            **dict(dec, H=8 * dec["H_kv"])
        )
        yield "decode_int8_g1", lambda: check_decode(**dec, int8=True)
        yield "decode_int8_g4", lambda: check_decode(**gqa, int8=True)
        yield "decode_int8_g4_stored", lambda: check_decode(
            **gqa, int8=True, depth=2
        )
        yield "decode_paged16_fp32", lambda: check_decode(**dec, page_size=16)
        yield "decode_paged16_int8_g4", lambda: check_decode(
            **gqa, int8=True, page_size=16
        )
        # A block-diffusion model (models/sdar.py): the block-causal
        # prefill mask; a block's 4 queries folded into the decode
        # kernel's group dimension (8 query heads a kv head become 32
        # rows) on the stored cache; the routed experts at the
        # benchmark's widths (128 lanes' positions, top-8 of 128).
        yield "flash_fwd_bwd_bf16_block_causal4", lambda: check_flash(
            **flash, causal=4
        )
        # The train cells' own call, and its forward on float32 inputs
        # (the whole-prompt prefill of a model that states fp32).
        cell = flash if tiny else CELL_FLASH
        yield "flash_fwd_bwd_bf16_causal_cell", lambda: check_flash(**cell)
        yield "flash_fwd_bwd_bf16_causal_cell_projection", lambda: check_flash(
            **cell, projection=True
        )
        # ... whose backward is ONE kernel over a resident head since PR
        # 39; the grid pair a longer head falls back to, through both
        # entries at the same shape; and the resident form where the
        # LSE is differentiated over fewer queries than keys.
        yield "flash_fwd_bwd_bf16_causal_cell_grid", lambda: check_flash(
            **cell, form="grid"
        )
        yield "flash_fwd_bwd_bf16_causal_cell_projection_grid", (
            lambda: check_flash(**cell, projection=True, form="grid"))
        yield "flash_backward_forms_agree_lse_rectangular", (
            lambda: check_backward_forms(**dict(cell, B=1, H=2)))
        yield "flash_fwd_fp32_causal_cell", lambda: check_flash(
            **cell, dtype="float32", backward=False
        )
        fold = (dict(S=2, H=32, H_kv=1, Dh=128, L=256, depth=2) if tiny
                else dict(S=32, H=128, H_kv=4, Dh=128, L=512, depth=3))
        yield "decode_fp32_g32_block_folded", lambda: check_decode(**fold)
        moe = (dict(N=16, d=128, f=128, E=8, top_k=2) if tiny
               else dict(N=128, d=2048, f=768, E=128, top_k=8))
        yield "moe_grouped_bf16", lambda: check_moe(**moe)
        # One chip's share of a wide expert layer (models/glm_dsa.py) at
        # the benchmark's widths: 16 of 256 sigmoid-routed experts of
        # [6144, 2048], a decode step's 16 tokens and a chunk's 2,048;
        # gate and up are walked a column block at a time. And a decode
        # step over the latent cache: 32 index heads of 128 scoring a
        # lane of 17,408, the top 2,048, 64 heads against one row of 576.
        share = (dict(d=128, f=512, E=16, held=4, first=4, top_k=4) if tiny
                 else dict(d=6144, f=2048, E=256, held=16, first=0, top_k=8))
        for n in ((16,) if tiny else (16, 2048)):
            yield f"moe_share_sigmoid_bf16_{n}", functools.partial(
                check_moe_share, N=n, **share)
        latent = (dict(S=3, H=4, R=16, Dr=8, Hi=2, Di=16, L=64, top_k=8)
                  if tiny else dict(S=16, H=64, R=512, Dr=64, Hi=32, Di=128,
                                    L=17408, top_k=2048))
        yield "latent_decode_selected_rows", lambda: check_latent_decode(
            **latent)
        # ... and a prefill chunk's attention there, the kernel against
        # the jnp walk, masks compared bit for bit.
        yield "latent_prefill_masked_walk", functools.partial(
            check_latent_prefill,
            **(LATENT_PREFILL_TINY if tiny else LATENT_PREFILL))
        # A hybrid of state-space and attention layers (models/
        # granite_hybrid.py) at the benchmark's widths: 4 queries a kv
        # head of 64 at softmax scale 1/64, two kv heads to a 128-lane
        # group of the stored rows; the state update of 64 lanes of 64
        # heads x 64 channels x 128 state dimensions, every fourth idle.
        packed = (dict(S=2, H=8, H_kv=2, Dh=64, L=256, depth=2) if tiny
                  else dict(S=64, H=32, H_kv=8, Dh=64, L=2048, depth=2))
        yield "decode_fp32_g4_dh64_packed", lambda: check_decode_packed(
            **packed, scale=1 / 64)
        upd = (dict(S=3, H=2, P=64, N=16, layers=3, live_every=2) if tiny
               else dict(S=64, H=64, P=64, N=128, layers=3, live_every=4))
        yield "ssm_state_update_fp32", lambda: check_ssm_update(**upd)
        # The SambaY decoder (models/sambay.py) at the benchmark's
        # widths: the two maps of 20 head pairs over 10 kv pairs, on a
        # window's ring of 512 rows (8 layers stored) and on the shared
        # rows of 4096; the per-element update of 64 lanes of [16,
        # 5120], every fourth idle; the prefill scan of a chunk of 512
        # with a padded end.
        diff = (dict(S=2, H=8, H_kv=4, depth=2) if tiny
                else dict(S=64, H=40, H_kv=20, depth=2))
        yield "decode_fp32_diff_dh64_ring", lambda: check_decode_diff(
            **diff, L=256 if tiny else 512, ring=True)
        yield "decode_fp32_diff_dh64_shared", lambda: check_decode_diff(
            **dict(diff, depth=1), L=256 if tiny else 4096, ring=False)
        sel = (dict(S=3, C=256, N=4, layers=3, live_every=2) if tiny
               else dict(S=64, C=5120, N=16, layers=3, live_every=4))
        yield "selective_state_update_fp32", lambda: check_selective_update(
            **sel)
        scan = (dict(T=40, C=256, N=4, real=33) if tiny
                else dict(T=512, C=5120, N=16, real=389))
        yield "selective_scan_fp32", lambda: check_selective_scan(**scan)
        # Both cells' decode calls at the positions their lanes hold.
        for name, cell in (DECODE_CELLS_TINY if tiny else DECODE_CELLS).items():
            yield f"decode_fp32_{name}_cell_positions", functools.partial(
                check_decode_cell, **cell)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--all", action="store_true")
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--time", action="store_true")
    p.add_argument("--only", default="", metavar="PREFIX",
                   help="run only the cases whose name starts with PREFIX")
    args = p.parse_args()

    from ddp_tpu.obs.recorder import build_info
    from ddp_tpu.ops.flash import pallas_kernel_mode
    from ddp_tpu.runtime.dist import enable_compile_cache

    enable_compile_cache()
    kernel = pallas_kernel_mode()
    print(json.dumps({"build_info": build_info(), "kernel": kernel}), flush=True)
    if args.time:
        shape = (dict(B=1, T=128, H=2, D=128, block=64) if args.tiny
                 else CELL_FLASH)
        runs = [(f"flash_time_{entry}" + (f"_{form}" if form else ""),
                 functools.partial(time_flash, **shape, entry=entry,
                                   form=form))
                for entry in TIME_ENTRIES for form in TIME_FORMS]
        runs += [(f"decode_time_{name}", functools.partial(time_decode, **cell))
                 for name, cell in (DECODE_CELLS_TINY if args.tiny
                                    else DECODE_CELLS).items()]
        runs.append(("latent_prefill_time_masked_walk", functools.partial(
            time_latent_prefill,
            **(LATENT_PREFILL_TINY if args.tiny else LATENT_PREFILL))))
    else:
        runs = cases(args.tiny, args.all)
    failed = []
    for name, run in runs:
        if not name.startswith(args.only):
            continue
        try:
            rec = run()
        except Exception:  # noqa: BLE001 — report every case, fail at the end
            rec = {"ok": False, "error": traceback.format_exc(limit=6)}
        print(json.dumps({"case": name, "kernel": kernel, **rec}), flush=True)
        if not rec["ok"]:
            failed.append(name)
    print(json.dumps({"kernels_ok": not failed, "failed": failed}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
