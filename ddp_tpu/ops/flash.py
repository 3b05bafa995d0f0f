"""Pallas TPU flash attention: fused forward AND backward kernels.

The hot op of the attention path, written for the hardware
(/opt/skills/guides/pallas_guide.md): Q and K/V blocks stream through
VMEM on a (batch·head, q-block, kv-block) grid, the online-softmax
recurrence lives in fp32 VMEM scratch that persists across the
innermost grid dimension, every matmul hits the MXU with
``preferred_element_type=jnp.float32``, and HBM traffic is O(T·D) —
the [T, S] score matrix never exists. This is the TPU-native answer to
the fused ATen attention kernels the reference inherits invisibly from
torch's C++ core (/root/reference/train_ddp.py:199, SURVEY.md §2b N5) —
there the fusion lives in cuDNN/ATen; here it is an explicit trio of
Pallas kernels.

Differentiation is flash end to end: the forward kernel also emits the
per-row log-sum-exp (LSE), and the backward runs two Pallas kernels —
one gridded over Q blocks producing dQ, one gridded over K/V blocks
producing dK/dV — each recomputing P = exp(S − LSE) blockwise from the
saved residuals. Peak memory of the whole VJP is O(T·D); the round-1
version recomputed backward through a dense O(T²) reference
(VERDICT.md "What's missing" #1).

Causal masking skips FLOPs: strictly-future (q-block, kv-block) cells
are ``pl.when``-gated off in all three kernels, so ~half the MXU work
disappears at large T.

``flash_attention_with_lse`` additionally returns the LSE rows, which
makes the kernel composable as the per-hop block primitive of ring
attention (parallel/ring.py): partial results from different KV blocks
merge by the standard (out, lse) log-space combine, and the custom VJP
routes the lse cotangent through the same blockwise backward (the
``delta − dlse`` fold below).

Layout notes (Mosaic constraints): per-row statistics (LSE, delta)
travel as [B·H, T, LANES] fp32 broadcast across a 128-lane minor
dimension — a [.., T, 1] layout would be lane-padded to 128 in VMEM
anyway, and 2-D [B·H, T] blocks of one row are not tileable. Scratch
accumulators persist across the innermost grid dimension and flush on
its last iteration (``pl.when``), the same scheme as
jax.experimental.pallas.ops.tpu.flash_attention.

``interpret=True`` runs the kernels on CPU for tests — the same
program the TPU compiles, minus Mosaic. On-chip agreement with the
dense reference is checked by ``scripts/check_kernels.py`` (run by
``chip_smoke.py``); the tolerances it found are in CHANGES.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Minor-most lanes of a TPU vector register; per-row stats are carried
# broadcast across this many lanes (see module docstring).
LANES = 128


def pallas_kernel_mode() -> str:
    """How a Pallas kernel called with ``interpret=None`` runs on this
    backend — for the records that must say so: compiled by Mosaic on
    a TPU, the Pallas interpreter anywhere else."""
    return (
        "pallas-compiled"
        if jax.default_backend() == "tpu"
        else "pallas-interpreted"
    )


def _row_stat(ref):
    """Read a [block, LANES] lane-broadcast stat as a [block, 1] column."""
    return ref[0][:, :1]


def _last_key(row, causal):
    """The last key the query at (end-anchored) position ``row`` sees.
    ``causal`` is True / 1 for the plain triangle (the row itself), or
    an int B > 1 for the BLOCK-causal mask: the end of the row's block
    of B positions, so key j is visible iff ``j // B <= row // B`` —
    bidirectional inside a block, causal between blocks. A plain
    function of its arguments: the kernels call it on iotas and on
    block corners, the tests on integers."""
    b = int(causal)
    return row if b <= 1 else row // b * b + (b - 1)


def _causal_mask(s, q_start, k_start, block_q, block_k, S_total, T_total,
                 causal=True):
    """End-anchored causal mask: query t sees keys up to t + S − T
    (the dense reference's tril(k=S−T); KV-cache convention for T≠S),
    or to the end of its block under a block-causal ``causal``."""
    rows = _last_key(q_start + (S_total - T_total) + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    ), causal)
    cols = k_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return jnp.where(rows >= cols, s, -jnp.inf)


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale, causal, block_q, block_k, T_total, S_total,
):
    """Grid (B·H, T/bq, S/bk): online softmax over streamed KV blocks."""
    j = pl.program_id(2)
    n_kb = pl.num_programs(2)
    q_start = pl.program_id(1) * block_q

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    if causal:
        # Fully-masked (strictly future) block: skip all compute.
        live = _last_key(
            q_start + block_q - 1 + (S_total - T_total), causal
        ) >= j * block_k
    else:
        live = True

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # [block_q, D]
        kb = k_ref[0].astype(jnp.float32)  # [block_k, D]
        vb = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if causal:
            s = _causal_mask(
                s, q_start, j * block_k, block_q, block_k, S_total, T_total,
                causal,
            )
        m = m_ref[...][:, :1]
        l = l_ref[...][:, :1]
        new_m = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        # A fully-masked ROW has new_m = -inf; exp(-inf − -inf) would
        # be NaN. Guard the shift.
        shift = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        p = jnp.exp(s - shift)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - shift), 0.0)
        acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(new_m, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_kb - 1)
    def _flush():
        m = m_ref[...][:, :1]
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse = jnp.where(
            l > 0.0,
            jnp.where(jnp.isfinite(m), m, 0.0)
            + jnp.log(jnp.maximum(l, 1e-30)),
            -jnp.inf,
        )
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape)


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, dq_acc,
    *, scale, causal, block_q, block_k, T_total, S_total,
):
    """Grid (B·H, T/bq, S/bk): dQ accumulates over streamed KV blocks.

    ``dl_ref`` holds delta' = rowsum(dO ∘ O) − dLSE; with P recomputed
    as exp(S − LSE), dS = P ∘ (dO·Vᵀ − delta') and dQ = scale · dS·K.
    """
    j = pl.program_id(2)
    n_kb = pl.num_programs(2)
    q_start = pl.program_id(1) * block_q

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    if causal:
        live = _last_key(
            q_start + block_q - 1 + (S_total - T_total), causal
        ) >= j * block_k
    else:
        live = True

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = _row_stat(lse_ref)
        lse = jnp.where(
            jnp.isfinite(lse), lse, 0.5 * jnp.finfo(jnp.float32).max
        )
        dl = _row_stat(dl_ref)
        s = scale * lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            s = _causal_mask(
                s, q_start, j * block_k, block_q, block_k, S_total, T_total,
                causal,
            )
        p = jnp.exp(s - lse)  # masked: exp(-inf) = 0
        dp = lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dl)
        dq_acc[...] = dq_acc[...] + lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == n_kb - 1)
    def _flush():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    k_ref, v_ref, q_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale, causal, block_q, block_k, T_total, S_total,
):
    """Grid (B·H, S/bk, T/bq): dK/dV accumulate over streamed Q blocks."""
    i = pl.program_id(2)
    n_qb = pl.num_programs(2)
    k_start = pl.program_id(1) * block_k

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if causal:
        # Last query row of this Q block must see the first key of
        # this K block: (i+1)·bq − 1 + S − T >= k_start.
        live = _last_key(
            (i + 1) * block_q - 1 + (S_total - T_total), causal
        ) >= k_start
    else:
        live = True

    @pl.when(live)
    def _compute():
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        qb = q_ref[0].astype(jnp.float32)
        dob = do_ref[0].astype(jnp.float32)
        lse = _row_stat(lse_ref)
        lse = jnp.where(
            jnp.isfinite(lse), lse, 0.5 * jnp.finfo(jnp.float32).max
        )
        dl = _row_stat(dl_ref)
        s = scale * lax.dot_general(
            qb, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if causal:
            s = _causal_mask(
                s, i * block_q, k_start, block_q, block_k, S_total, T_total,
                causal,
            )
        p = jnp.exp(s - lse)
        dv_acc[...] = dv_acc[...] + lax.dot_general(
            p, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = lax.dot_general(
            dob, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dl)
        dk_acc[...] = dk_acc[...] + lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(i == n_qb - 1)
    def _flush():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def pick_block(n: int, requested: int, dtype) -> int:
    """Effective block along a length-``n`` streamed dimension.

    The grid needs ``block | n`` and Mosaic needs the block's row
    count to be a whole number of ``dtype`` sublane tiles (8 rows of
    4-byte, 16 of 2-byte, 32 of 1-byte elements) unless the block IS
    the whole dimension. So: ``n`` itself when it fits the request,
    else the largest tile-aligned divisor of ``n`` ≤ ``requested``.
    When none exists this raises with the shape named — the
    alternatives are an unaligned block the compiler rejects or one
    whole-length block that can pass the VMEM limit.
    """
    if n <= requested:
        return n
    align = 32 // jnp.dtype(dtype).itemsize
    for block in range(requested - requested % align, 0, -align):
        if n % block == 0:
            return block
    raise ValueError(
        f"no {jnp.dtype(dtype).name} block for a length-{n} dimension: "
        f"it has no divisor <= {requested} that is a multiple of "
        f"{align} rows — pad the length to a multiple of {align}"
    )


def _pick_blocks(T, S, block_q, block_k, dtype):
    return pick_block(T, block_q, dtype), pick_block(S, block_k, dtype)


def _to_bh(x):
    """[B, T, H, D] → [B·H, T, D]: one grid row per (batch, head)."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _flash_forward(
    q, k, v, *, causal: bool, block_q: int, block_k: int, interpret: bool
):
    """Returns (out [B,T,H,D], lse [B,T,H] fp32)."""
    B, T, H, D = q.shape
    S = k.shape[1]
    block_q, block_k = _pick_blocks(T, S, block_q, block_k, q.dtype)
    scale = D**-0.5
    qt, kt, vt = _to_bh(q), _to_bh(k), _to_bh(v)

    kw = {"memory_space": pltpu.VMEM}
    qmap = lambda b, i, j: (b, i, 0)
    kmap = lambda b, i, j: (b, j, 0)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, T_total=T, S_total=S,
        ),
        grid=(B * H, T // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), qmap, **kw),
            pl.BlockSpec((1, block_k, D), kmap, **kw),
            pl.BlockSpec((1, block_k, D), kmap, **kw),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), qmap, **kw),
            pl.BlockSpec((1, block_q, LANES), qmap, **kw),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, T, LANES), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((block_q, D)),
            _scratch((block_q, LANES)),
            _scratch((block_q, LANES)),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    out = out.reshape(B, H, T, D).transpose(0, 2, 1, 3)
    lse = lse[:, :, 0].reshape(B, H, T).transpose(0, 2, 1)  # [B, T, H]
    return out, lse


def _to_lanes(x_bth):
    """[B, T, H] per-row stat → [B·H, T, LANES] lane-broadcast fp32."""
    B, T, H = x_bth.shape
    flat = x_bth.astype(jnp.float32).transpose(0, 2, 1).reshape(B * H, T, 1)
    return jnp.broadcast_to(flat, (B * H, T, LANES))


def _flash_backward(
    q, k, v, out, lse, g, dlse, *, causal, block_q, block_k, interpret
):
    """Blockwise VJP: (dq, dk, dv) with O(T·D) peak memory.

    ``dlse`` is the cotangent of the LSE output (zeros when the caller
    only differentiates the attention output): dS picks up an extra
    +P·dLSE term, folded in as delta' = rowsum(dO ∘ O) − dLSE.
    """
    B, T, H, D = q.shape
    S = k.shape[1]
    block_q, block_k = _pick_blocks(T, S, block_q, block_k, q.dtype)
    scale = D**-0.5
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    dl_l = _to_lanes(delta - dlse.astype(jnp.float32))
    lse_l = _to_lanes(lse)
    qt, kt, vt, gt = _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(g)

    kw = {"memory_space": pltpu.VMEM}
    common = dict(
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        T_total=T, S_total=S,
    )
    qmap = lambda b, i, j: (b, i, 0)
    kmap = lambda b, i, j: (b, j, 0)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(B * H, T // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), qmap, **kw),
            pl.BlockSpec((1, block_k, D), kmap, **kw),
            pl.BlockSpec((1, block_k, D), kmap, **kw),
            pl.BlockSpec((1, block_q, D), qmap, **kw),
            pl.BlockSpec((1, block_q, LANES), qmap, **kw),
            pl.BlockSpec((1, block_q, LANES), qmap, **kw),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), qmap, **kw),
        out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
        scratch_shapes=[_scratch((block_q, D))],
        interpret=interpret,
        name="flash_dq",
    )(qt, kt, vt, gt, lse_l, dl_l)

    # For dK/dV the K block is the OUTER streamed dim, Q the inner.
    kvmap = lambda b, jk, i: (b, jk, 0)
    qmap2 = lambda b, jk, i: (b, i, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(B * H, S // block_k, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_k, D), kvmap, **kw),
            pl.BlockSpec((1, block_k, D), kvmap, **kw),
            pl.BlockSpec((1, block_q, D), qmap2, **kw),
            pl.BlockSpec((1, block_q, D), qmap2, **kw),
            pl.BlockSpec((1, block_q, LANES), qmap2, **kw),
            pl.BlockSpec((1, block_q, LANES), qmap2, **kw),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), kvmap, **kw),
            pl.BlockSpec((1, block_k, D), kvmap, **kw),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, S, D), v.dtype),
        ],
        scratch_shapes=[_scratch((block_k, D)), _scratch((block_k, D))],
        interpret=interpret,
        name="flash_dkv",
    )(kt, vt, qt, gt, lse_l, dl_l)

    back = lambda x, T_: x.reshape(B, H, T_, D).transpose(0, 2, 1, 3)
    return back(dq, T), back(dk, S), back(dv, S)


def _reference(q, k, v, causal: bool):
    """Dense XLA attention — the math the kernels implement, for tests
    and the non-Pallas fallback. fp32 accumulation throughout."""
    dtype = q.dtype
    scale = q.shape[-1] ** -0.5
    logits = (
        jnp.einsum(
            "bthd,bshd->bhts", q.astype(jnp.float32), k.astype(jnp.float32)
        )
        * scale
    )
    if causal:
        T, S = logits.shape[-2:]
        rows = _last_key(jnp.arange(T)[:, None] + (S - T), causal)
        logits = jnp.where(rows >= jnp.arange(S)[None, :], logits, -jnp.inf)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", weights, v.astype(jnp.float32))
    return out.astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
):
    """Flash attention on [B, T, H, D]; Pallas forward AND backward.

    ``interpret=True`` for CPU (tests); on TPU the kernels compile via
    Mosaic. Use keyword-style through ``make_flash_attention`` for the
    model-facing ``(q, k, v) -> out`` contract. ``causal`` may be an
    int B > 1: the block-causal mask of :func:`_last_key` (a block-
    diffusion prefill; B divides the chunk, so whole blocks stay
    inside a query tile).
    """
    out, _ = _flash_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_backward(
        q, k, v, out, lse, g, jnp.zeros_like(lse), causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_with_lse(
    q,
    k,
    v,
    causal: bool = False,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
):
    """Like ``flash_attention`` but returns ``(out, lse)``.

    ``lse`` is [B, T, H] fp32 = logsumexp of the scaled logits per
    query row. Partial attention outputs over different KV blocks
    combine exactly from (out, lse) pairs — this is the per-hop
    primitive of ring attention (parallel/ring.py). Differentiable in
    both outputs.
    """
    return _flash_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


def _fal_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return (out, lse), (q, k, v, out, lse)


def _fal_bwd(causal, block_q, block_k, interpret, residuals, cotangents):
    q, k, v, out, lse = residuals
    g, dlse = cotangents
    return _flash_backward(
        q, k, v, out, lse, g, dlse, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


flash_attention_with_lse.defvjp(_fal_fwd, _fal_bwd)


def make_flash_attention(
    *, causal: bool = False, block_q: int = 512, block_k: int = 512,
    interpret: bool | None = None,
):
    """Bind options → the framework's ``(q, k, v) -> out`` attention fn.

    ``interpret=None`` auto-detects: compiled kernel on TPU, interpreter
    elsewhere (CPU dev boxes), so the same model config runs anywhere.
    """

    def fn(q, k, v):
        interp = interpret
        if interp is None:
            interp = jax.default_backend() != "tpu"
        return flash_attention(q, k, v, causal, block_q, block_k, interp)

    return fn
