"""Pallas TPU flash attention: fused forward AND backward kernels.

The hot op of the attention path, written for the hardware
(/opt/skills/guides/pallas_guide.md): Q and K/V blocks stream through
VMEM on a (batch·head, live block pair) grid, the online-softmax
recurrence lives in fp32 VMEM scratch that persists across the pairs
of an output block, every matmul hits the MXU with
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

Causal masking skips arithmetic, grid steps and fetches (PR 31). Every
(q block, k block) pair is classified from the static shapes
(``_classify``: *interior*, *diagonal* or *dead*, by the block corners
under the same ``_last_key`` the mask applies). The grid's second
dimension runs over the LIVE pairs only, through scalar-prefetched
(outer, inner, flags) tables (``_live_pairs``): a strictly-future pair
is no grid step, so its blocks are never fetched — 10 steps of 16 at
2048 x 2048 in blocks of 512, in all three kernels. Of the live pairs
only the diagonal ones run the program with the mask (iota, compare,
select); interior pairs run the plain one, and the guards against a
row with no visible key are built only where such a row can exist
(``T > S``). Each traced ``pallas_call`` leaves a ``flash.plan`` record
in the tracer's ring (``obs/tracer.py``): blocks, steps visited, of
which masked, of which dead.

What a step does NOT pay for, found on the chip (PERF.md section 6, PR
31): a cross-lane sum (the forward's ``l`` travels as lane-partial
sums, reduced once a q block), lane broadcasts of [block, 1] columns
(the forward's statistics stay [block_q, LANES]), and transposes of
the [block_q, block_k] tiles on their way to the MXU (the dK/dV kernel
builds its tile keys first). MXU operands are float32 copies of the
blocks: at the default precision the MXU takes them in one bfloat16
pass, bit for bit what explicit bfloat16 operands give, and the
explicit casts measured no faster.

``flash_attention_with_lse`` additionally returns the LSE rows, which
makes the kernel composable as the per-hop block primitive of ring
attention (parallel/ring.py): partial results from different KV blocks
merge by the standard (out, lse) log-space combine, and the custom VJP
routes the lse cotangent through the same blockwise backward (the
``delta − dlse`` fold below).

Where the operands lie (PR 33). A kernel reads an operand where its
producer wrote it and writes a result where its consumer reads it: an
operand is an array [B, T, columns] and a rule that maps a head to a
D-wide column block (``_Operand``), and the grid's first dimension
still runs over (batch, head) — ``_spec`` is the one index rule of all
three kernels. Two entries share them. ``flash_attention_projection``
takes the fused head-major projection [B, T, H·3·D] as
``models/vit.py``'s ``qkv`` matmul wrote it (one array seen three
times, stride 3, offsets 0, 1, 2), writes ``out`` as [B, T, H·D], which
is what ``proj`` reads, and its backward writes dq, dk, dv into ONE
[B, T, H·3·D] array, the projection's cotangent (``flash_dq`` writes
its column blocks of it, ``flash_dkv`` takes it aliased and writes a
head's dk | dv beside them). ``flash_attention`` /
``flash_attention_with_lse`` take separate
[B, T, H, D] operands (the ring's hops, GQA after its repeat), which
are [B, T, H·D] by a free reshape. Nothing is sliced, transposed,
stacked or broadcast in XLA around the kernels on either. That needs a
head of whole 128-lane groups; a narrower head (ViT-Tiny's 64) is no
lane-aligned column block and goes through both entries as transposed
[B·H, T, D] copies (no cell runs that: ``FLASH_MIN_LEN`` keeps the
image models on the dense path). Each ``flash.plan`` record says
which: ``projection``, ``heads_last`` or ``transposed``.

Layout notes (Mosaic constraints): per-row statistics (LSE, delta')
travel as [B·H, T, LANES] fp32 broadcast across a 128-lane minor
dimension — a [.., T, 1] layout would be lane-padded to 128 in VMEM
anyway, and 2-D [B·H, T] blocks of one row are not tileable. They stay
so from kernel to kernel: the custom VJP's residual is the LSE as
``flash_fwd`` wrote it, and delta' = rowsum(dO ∘ O) − dLSE is made by
``flash_dq`` on the first step of each q block, from the dO and O
blocks it holds, and handed to ``flash_dkv``. Scratch
accumulators persist across the steps of an output block and flush on
its last one (``pl.when`` on the step's flags), the scheme of
jax.experimental.pallas.ops.tpu.flash_attention. The live-pair tables
sit in scalar memory, 12 bytes a pair: T/block_q x S/block_k entries at
most (4,096 at 32k x 32k in blocks of 512).

``interpret=True`` runs the kernels on CPU for tests — the same
program the TPU compiles, minus Mosaic. On-chip agreement with the
dense reference is checked by ``scripts/check_kernels.py`` (run by
``chip_smoke.py``; ``--time`` prints the three kernels' device ms a
call at the train cells' shape); the tolerances it found are in
CHANGES.md.
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ddp_tpu.obs.tracer import get_tracer, importing

with importing("jax.experimental.pallas"):
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
    if causal <= 1:  # True is the plain triangle too
        return row
    return row // causal * causal + (causal - 1)


def _causal_mask(s, q_start, k_start, block_q, block_k, S_total, T_total,
                 causal=True, keys_first=False):
    """End-anchored causal mask: query t sees keys up to t + S − T
    (the dense reference's tril(k=S−T); KV-cache convention for T≠S),
    or to the end of its block under a block-causal ``causal``. ``s``
    is [block_q, block_k], or its transpose with ``keys_first``."""
    shape = (block_k, block_q) if keys_first else (block_q, block_k)
    rows = _last_key(q_start + (S_total - T_total) + lax.broadcasted_iota(
        jnp.int32, shape, int(keys_first)
    ), causal)
    cols = k_start + lax.broadcasted_iota(jnp.int32, shape, 1 - keys_first)
    return jnp.where(rows >= cols, s, -jnp.inf)


# What a grid step's entry in the scalar-prefetched ``flags`` table
# says: the step is the first / the last of its output block (zero the
# accumulators / write the block out), and its block pair's class.
_FIRST, _LAST, _DIAGONAL, _DEAD = 1, 2, 4, 8


def _classify(T, S, block_q, block_k, causal):
    """Class of every (q block, k block) pair, ``T/bq`` rows of ``S/bk``:
    0 (*interior*: the tile's first query row already sees its last
    key, so no element is masked), ``_DIAGONAL`` (only part of the tile
    is visible) or ``_DEAD`` (no query row sees the tile's first key).
    Decided on the block corners by the ``_last_key`` the mask itself
    applies, so the end-anchored ``T != S`` and the block-causal cases
    are classified as they are masked; without ``causal`` every pair
    is interior. Python ints from static shapes: trace time only."""
    n_q, n_k = T // block_q, S // block_k
    classes = [[0] * n_k for _ in range(n_q)]
    if not causal:
        return classes
    for i, row in enumerate(classes):
        first_row = i * block_q + (S - T)
        sees_first = _last_key(first_row, causal)
        sees_last = _last_key(first_row + block_q - 1, causal)
        for j in range(n_k):
            if sees_last < j * block_k:
                row[j] = _DEAD
            elif sees_first < (j + 1) * block_k - 1:
                row[j] = _DIAGONAL
    return classes


def _live_pairs(classes, *, by_key: bool = False):
    """The grid's second dimension: ``(outer, inner, flags)`` lists
    with one entry per LIVE block pair of ``classes``, grouped by
    output block (the q block; the k block with ``by_key``, for dK/dV)
    in ascending order. A dead pair is no grid step, so it is neither
    visited nor fetched. An output block with no live pair at all
    (query rows before the first key, ``T > S``) keeps one entry
    flagged ``_DEAD``: its step only zeroes and writes the block."""
    if by_key:
        classes = list(zip(*classes))
    outer, inner, flags = [], [], []
    for o, row in enumerate(classes):
        live = [n for n, c in enumerate(row) if c != _DEAD] or [0]
        outer += [o] * len(live)
        inner += live
        flags += [row[n] for n in live]
        flags[-len(live)] |= _FIRST
        flags[-1] |= _LAST
    return outer, inner, flags


def _by_class(flags, causal, body):
    """Run ``body(masked)`` once for a live step: the masked program on
    a diagonal pair, the plain one (no iota, compare or select) on an
    interior pair, nothing on a dead one. Without ``causal`` there is
    no diagonal pair and the masked program is not even built."""
    if causal:
        pl.when(flags & _DIAGONAL != 0)(functools.partial(body, True))
    pl.when(flags & (_DIAGONAL | _DEAD) == 0)(functools.partial(body, False))


def _dot(a, b, contract):
    """``a`` · ``b`` over the dimension pair ``contract``, accumulated
    in float32. The operands are float32 too; at the default precision
    the MXU takes them in ONE bfloat16 pass (bit for bit what explicit
    bfloat16 operands give on a TPU v5e: PERF.md section 6, PR 31)."""
    return lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _over_lanes(stat, n):
    """A [rows, LANES] lane-broadcast statistic as it meets a [rows, n]
    tile: whole lane groups repeated where n allows, else a column."""
    if n % LANES:
        return stat[:, :1]
    return stat if n == LANES else jnp.concatenate(
        [stat] * (n // LANES), axis=1)


def _lane_partial_sum(p):
    """[rows, n] → [rows, LANES] whose lanes sum to each row's sum: the
    cross-lane reduction is left to whoever reads the total."""
    rows, n = p.shape
    if n % LANES:
        return jnp.broadcast_to(
            p.sum(axis=-1, keepdims=True) * (1.0 / LANES), (rows, LANES))
    return functools.reduce(
        jnp.add, (p[:, c:c + LANES] for c in range(0, n, LANES)))


def _fwd_kernel(
    qi_ref, kj_ref, flags_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
    acc_ref, m_ref, l_ref,
    *, scale, causal, block_q, block_k, T_total, S_total,
):
    """Grid (B·H, live pairs by q block): online softmax over the
    streamed KV blocks of each q block.

    The running statistics stay [block_q, LANES] from step to step:
    ``m_ref`` the row maximum on every lane, ``l_ref`` PARTIAL row sums
    (its lanes add up to the row's), so that a step pays neither a
    cross-lane sum nor a lane broadcast of a [block_q, 1] column; the
    flush reduces ``l`` once a q block."""
    step = pl.program_id(1)
    flags = flags_ref[step]
    q_start, k_start = qi_ref[step] * block_q, kj_ref[step] * block_k
    # Only with more queries than keys does a row see no key at all.
    empty_rows = bool(causal) and T_total > S_total

    @pl.when(flags & _FIRST != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute(masked):
        q = q_ref[0].astype(jnp.float32) * scale  # [block_q, D]
        s = _dot(q, k_ref[0], (1, 1))  # [block_q, block_k]
        if masked:
            s = _causal_mask(
                s, q_start, k_start, block_q, block_k, S_total, T_total,
                causal,
            )
        m = m_ref[...]
        new_m = jnp.maximum(m, jnp.broadcast_to(
            s.max(axis=-1, keepdims=True), m.shape))
        shift, corr = new_m, jnp.exp(m - new_m)
        if empty_rows:
            # A fully-masked ROW has new_m = -inf; exp(-inf − -inf)
            # would be NaN. Guard the shift.
            shift = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - shift), 0.0)
        p = jnp.exp(s - _over_lanes(shift, block_k))
        acc_ref[...] = acc_ref[...] * _over_lanes(
            corr, acc_ref.shape[1]) + _dot(p, v_ref[0], (1, 0))
        l_ref[...] = l_ref[...] * corr + _lane_partial_sum(p)
        m_ref[...] = new_m

    _by_class(flags, causal, _compute)

    @pl.when(flags & _LAST != 0)
    def _flush():
        m = m_ref[...][:, :1]
        l = l_ref[...].sum(axis=-1, keepdims=True)
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        if empty_rows:
            lse = jnp.where(l > 0.0, lse, -jnp.inf)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape)


def _saved_lse(lse, empty_rows):
    """The forward's LSE as the backward subtracts it: a row that saw
    no key saved -inf, and must give P = exp(S − LSE) = 0, not NaN."""
    if not empty_rows:
        return lse
    return jnp.where(jnp.isfinite(lse), lse, 0.5 * jnp.finfo(jnp.float32).max)


def _dq_kernel(
    qi_ref, kj_ref, flags_ref, q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
    *rest, scale, causal, block_q, block_k, T_total, S_total,
):
    """Grid (B·H, live pairs by q block): dQ accumulates over the
    streamed KV blocks of each q block.

    The first step of a q block also makes its rows of delta' =
    rowsum(dO ∘ O) − dLSE from the dO and O blocks it holds, lane-
    broadcast into ``dl_ref``: an OUTPUT ([B·H, T, LANES], what
    ``flash_dkv`` reads) that this kernel reads back on every step of
    the block. ``rest`` is ``dq_ref, dl_ref`` and the accumulator, led
    by ``dlse_ref`` (lane-broadcast like the LSE) where the caller
    differentiates the LSE output too. With P recomputed as
    exp(S − LSE), dS = P ∘ (dO·Vᵀ − delta') and dQ = scale · dS·K.
    """
    *dlse_ref, dq_ref, dl_ref, dq_acc = rest
    step = pl.program_id(1)
    flags = flags_ref[step]
    q_start, k_start = qi_ref[step] * block_q, kj_ref[step] * block_k
    empty_rows = bool(causal) and T_total > S_total

    @pl.when(flags & _FIRST != 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        delta = (
            do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        ).sum(axis=-1, keepdims=True)
        dl = jnp.broadcast_to(delta, dl_ref.shape[1:])
        dl_ref[0] = dl - dlse_ref[0][0] if dlse_ref else dl

    def _compute(masked):
        q = q_ref[0].astype(jnp.float32) * scale
        s = _dot(q, k_ref[0], (1, 1))  # [block_q, block_k]
        if masked:
            s = _causal_mask(
                s, q_start, k_start, block_q, block_k, S_total, T_total,
                causal,
            )
        # masked: exp(-inf) = 0
        p = jnp.exp(s - _saved_lse(_row_stat(lse_ref), empty_rows))
        dp = _dot(do_ref[0], v_ref[0], (1, 1))
        ds = p * (dp - _row_stat(dl_ref))
        dq_acc[...] = dq_acc[...] + _dot(ds, k_ref[0], (1, 0))

    _by_class(flags, causal, _compute)

    @pl.when(flags & _LAST != 0)
    def _flush():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    kj_ref, qi_ref, flags_ref, k_ref, v_ref, q_ref, do_ref, lse_ref, dl_ref,
    *rest, scale, causal, block_q, block_k, T_total, S_total, joined=False,
):
    """Grid (B·H, live pairs by k block): dK/dV accumulate over the
    streamed Q blocks of each k block.

    ``rest`` is ``dk_ref, dv_ref`` and the two accumulators, or, when
    ``joined`` (the fused projection), the cotangent array itself
    (aliased to the output, in no memory the kernel reads), ``dkv_ref``
    and the accumulators: the flush then writes the head's
    [block_k, 2·D] dk | dv columns of the projection's cotangent in
    one block.

    Where the q block fills whole lane groups the tile is built KEYS
    FIRST, Sᵀ = K·Qᵀ [block_k, block_q]: then Pᵀ and dSᵀ enter the two
    accumulating matmuls as they stand (queries first, each would be
    transposed on its way to the MXU) and the row statistics are
    [1, block_q] rows that meet the tile along the sublanes."""
    step = pl.program_id(1)
    flags = flags_ref[step]
    q_start, k_start = qi_ref[step] * block_q, kj_ref[step] * block_k
    empty_rows = bool(causal) and T_total > S_total
    keys_first = block_q % LANES == 0
    *out_refs, dk_acc, dv_acc = rest

    @pl.when(flags & _FIRST != 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute(masked):
        # q carries the scale, and so does the dK it accumulates into
        q = q_ref[0].astype(jnp.float32) * scale
        if keys_first:
            stat = lambda ref: ref[0].T[:1]  # [1, block_q]
            swap = lambda a, b: (b, a)
            over_queries = 1
        else:
            stat = _row_stat  # [block_q, 1]
            swap = lambda a, b: (a, b)
            over_queries = 0
        s = _dot(*swap(q, k_ref[0]), (1, 1))
        if masked:
            s = _causal_mask(
                s, q_start, k_start, block_q, block_k, S_total, T_total,
                causal, keys_first,
            )
        p = jnp.exp(s - _saved_lse(stat(lse_ref), empty_rows))
        dv_acc[...] = dv_acc[...] + _dot(p, do_ref[0], (over_queries, 0))
        dp = _dot(*swap(do_ref[0], v_ref[0]), (1, 1))
        ds = p * (dp - stat(dl_ref))
        dk_acc[...] = dk_acc[...] + _dot(ds, q, (over_queries, 0))

    _by_class(flags, causal, _compute)

    @pl.when(flags & _LAST != 0)
    def _flush():
        D = dk_acc.shape[1]
        if joined:
            _, dkv_ref = out_refs
            dkv_ref[0, :, :D] = dk_acc[...].astype(dkv_ref.dtype)
            dkv_ref[0, :, D:] = dv_acc[...].astype(dkv_ref.dtype)
        else:
            dk_ref, dv_ref = out_refs
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
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
    """[B, T, H, D] → [B·H, T, D]: one grid row per (batch, head). Only
    where a head is not whole 128-lane groups (``_operand``)."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


class _Operand(NamedTuple):
    """What a kernel reads or writes: ``array`` [B', T, columns] as its
    producer wrote it, and the rule that finds a head's D-wide column
    block in it — head ``h`` of the ``heads`` a row holds lies at block
    ``h * stride + offset``. The fused projection is one array seen
    three times (stride 3, offsets 0, 1, 2); [B, T, H, D] is [B, T, H·D]
    by a free reshape (stride 1, offset 0); the transposed [B·H, T, D]
    holds one head a row."""

    array: jax.Array
    heads: int
    stride: int = 1
    offset: int = 0


def _layout(D):
    """How separate [B, T, H, D] operands reach the kernels. A D-wide
    column block of [B, T, H·D] is lane-aligned only where D is whole
    128-lane groups; a narrower head goes as a transposed copy."""
    return "transposed" if D % LANES else "heads_last"


def _operand(x):
    """[B, T, H, D] as the kernels take it (``_layout``)."""
    B, T, H, D = x.shape
    if D % LANES:
        return _Operand(_to_bh(x), 1)
    return _Operand(x.reshape(B, T, H * D), H)


def _from_operand(x, B, H):
    """A result in ``_operand``'s layout back as [B, T, H, D]."""
    T, D = x.shape[1], x.shape[2] * x.shape[0] // (B * H)
    if D % LANES:
        return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)
    return x.reshape(B, T, H, D)


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


_OUTER, _INNER = 0, 1


def _spec(rows, width, table, heads=1, stride=1, offset=0):
    """BlockSpec of a [rows, width] block of a [B', T, columns] array,
    under THE index rule of the three kernels: grid row ``bh`` is head
    ``bh % heads`` of batch row ``bh // heads``, the row block is the
    step's entry in ``table`` — ``_OUTER`` for an operand blocked like
    the output (the q block in forward and dQ, the k block in dK/dV),
    ``_INNER`` for one streamed under it — and the column block is the
    head's (``_Operand``). The defaults are a [B·H, T, width] array."""

    def index(bh, n, *tables):
        return (lax.div(bh, heads), tables[table][n],
                lax.rem(bh, heads) * stride + offset)

    return pl.BlockSpec((1, rows, width), index, memory_space=pltpu.VMEM)


def _operand_spec(rows, D, table, operand):
    return _spec(rows, D, table, *operand[1:])


def _plan(kernel, T, S, block_q, block_k, causal, layout, *, by_key=False):
    """The live-pair tables of one ``pallas_call``, and its
    ``flash.plan`` record in the tracer's ring (trace time: a compiled
    step leaves none): the grid steps a (batch·head) visits, how many
    of them run the masked program, how many are dead, the dtype the
    MXU's operands are handed over in, and where the operands lie
    (``projection``, ``heads_last`` or ``transposed``)."""
    tables = _live_pairs(
        _classify(T, S, block_q, block_k, causal), by_key=by_key)
    flags = tables[2]
    get_tracer().complete(
        "flash.plan", time.perf_counter(), 0.0,
        nums=(kernel, block_q, block_k, len(flags),
              sum(1 for f in flags if f & _DIAGONAL),
              sum(1 for f in flags if f & _DEAD), "float32", layout),
    )
    return tuple(jnp.asarray(t, jnp.int32) for t in tables)


def _forward_call(
    q, k, v, D, layout, *, causal, block_q: int, block_k: int, interpret
):
    """``flash_fwd`` on ``_Operand``s. Returns (out [B', T, heads·D] in
    the layout of a stride-1 operand, lse [B·H, T, LANES] fp32)."""
    Bp, T, _ = q.array.shape
    S = k.array.shape[1]
    heads = q.heads
    block_q, block_k = _pick_blocks(T, S, block_q, block_k, q.array.dtype)
    by_q = _plan("flash_fwd", T, S, block_q, block_k, causal, layout)
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=D**-0.5, causal=causal, block_q=block_q,
            block_k=block_k, T_total=T, S_total=S,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Bp * heads, len(by_q[0])),
            in_specs=[
                _operand_spec(block_q, D, _OUTER, q),
                _operand_spec(block_k, D, _INNER, k),
                _operand_spec(block_k, D, _INNER, v),
            ],
            out_specs=[
                _spec(block_q, D, _OUTER, heads),
                _spec(block_q, LANES, _OUTER),
            ],
            scratch_shapes=[
                _scratch((block_q, D)),
                _scratch((block_q, LANES)),
                _scratch((block_q, LANES)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bp, T, heads * D), q.array.dtype),
            jax.ShapeDtypeStruct((Bp * heads, T, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*by_q, q.array, k.array, v.array)


def _backward_calls(
    q, k, v, g, out, lse, D, layout, *, causal, block_q, block_k, interpret,
    dlse=None, joined: bool = False,
):
    """``flash_dq`` and ``flash_dkv`` on ``_Operand``s; ``out`` is the
    forward's, ``lse`` [B·H, T, LANES] fp32 as it wrote it, ``dlse`` the
    LSE output's cotangent in the same layout or None. Returns (dq, dk,
    dv), each like a stride-1 operand, or with ``joined`` ONE
    [B', T, heads·3·D] array, the fused projection's cotangent:
    ``flash_dq`` writes a head's dq columns of it, ``flash_dkv`` the dk
    and dv columns of the same buffer. delta' travels from ``flash_dq``,
    which makes it, to ``flash_dkv`` as [B·H, T, LANES] fp32."""
    Bp, T, _ = q.array.shape
    S = k.array.shape[1]
    heads = q.heads
    dtype = q.array.dtype
    block_q, block_k = _pick_blocks(T, S, block_q, block_k, dtype)
    by_q = _plan("flash_dq", T, S, block_q, block_k, causal, layout)
    by_k = _plan(
        "flash_dkv", T, S, block_q, block_k, causal, layout, by_key=True)
    common = dict(
        scale=D**-0.5, causal=causal, block_q=block_q, block_k=block_k,
        T_total=T, S_total=S,
    )
    grid_rows = Bp * heads
    stats = [lse] if dlse is None else [lse, dlse]
    # dq lies as q does: with ``joined`` the first of a head's three
    # column blocks of the cotangent, whose others ``flash_dkv`` fills
    dq_stride = 3 if joined else 1
    dq, dl = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(grid_rows, len(by_q[0])),
            in_specs=[
                _operand_spec(block_q, D, _OUTER, q),
                _operand_spec(block_k, D, _INNER, k),
                _operand_spec(block_k, D, _INNER, v),
                _operand_spec(block_q, D, _OUTER, g),
                _operand_spec(block_q, D, _OUTER, out),
                *(_spec(block_q, LANES, _OUTER) for _ in stats),
            ],
            out_specs=[
                _spec(block_q, D, _OUTER, heads, dq_stride),
                _spec(block_q, LANES, _OUTER),
            ],
            scratch_shapes=[_scratch((block_q, D))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bp, T, heads * dq_stride * D), dtype),
            jax.ShapeDtypeStruct(lse.shape, jnp.float32),
        ],
        interpret=interpret,
        name="flash_dq",
    )(*by_q, q.array, k.array, v.array, g.array, out.array, *stats)

    # For dK/dV the K block is the OUTER streamed dim, Q the inner.
    in_specs = [
        _operand_spec(block_k, D, _OUTER, k),
        _operand_spec(block_k, D, _OUTER, v),
        _operand_spec(block_q, D, _INNER, q),
        _operand_spec(block_q, D, _INNER, g),
        _spec(block_q, LANES, _INNER),
        _spec(block_q, LANES, _INNER),
    ]
    aliases = {}
    if joined:
        # T == S: q, k and v are one array's columns, and so are their
        # cotangents. ``flash_dq`` wrote its column blocks of that
        # array; this call takes it as its own output (aliased, never
        # fetched) and writes a head's dk | dv beside them: 2·D columns
        # from column (3·head + 1)·D on, which is no whole multiple of
        # the block's width, hence an element offset and not a block
        # index.
        aliases = {3 + len(in_specs): 0}  # operands count the 3 tables
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))

        def dk_dv_columns(bh, n, *tables):
            return (lax.div(bh, heads),
                    pl.multiple_of(tables[_OUTER][n] * block_k, block_k),
                    pl.multiple_of((lax.rem(bh, heads) * 3 + 1) * D, D))

        out_specs = pl.BlockSpec(
            (pl.Element(1), pl.Element(block_k), pl.Element(2 * D)),
            dk_dv_columns, memory_space=pltpu.VMEM)
        out_shape = jax.ShapeDtypeStruct((Bp, S, heads * 3 * D), dtype)
    else:
        out_specs = [_spec(block_k, D, _OUTER, heads) for _ in range(2)]
        out_shape = [jax.ShapeDtypeStruct((Bp, S, heads * D), dtype)] * 2
    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common, joined=joined),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(grid_rows, len(by_k[0])),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[_scratch((block_k, D)), _scratch((block_k, D))],
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="flash_dkv",
    )(*by_k, k.array, v.array, q.array, g.array, lse, dl,
      *((dq,) if joined else ()))
    return dkv if joined else (dq, *dkv)


def _to_lanes(x_bth):
    """[B, T, H] per-row stat → [B·H, T, LANES] lane-broadcast fp32."""
    B, T, H = x_bth.shape
    flat = x_bth.astype(jnp.float32).transpose(0, 2, 1).reshape(B * H, T, 1)
    return jnp.broadcast_to(flat, (B * H, T, LANES))


def _lse_rows(lse, B):
    """The forward's [B·H, T, LANES] LSE as a caller takes it:
    [B, T, H]."""
    BH, T, _ = lse.shape
    return lse[:, :, 0].reshape(B, BH // B, T).transpose(0, 2, 1)


def _flash_forward(q, k, v, **opts):
    """Separate [B, T, H, D] operands. Returns (out [B, T, H, D], lse
    [B·H, T, LANES] fp32, as ``flash_fwd`` wrote it and the backward
    kernels read it)."""
    B, _, H, D = q.shape
    out, lse = _forward_call(
        _operand(q), _operand(k), _operand(v), D, _layout(D), **opts)
    return _from_operand(out, B, H), lse


def _flash_backward(q, k, v, out, lse, g, dlse=None, **opts):
    """Blockwise VJP of ``_flash_forward``: (dq, dk, dv) with O(T·D)
    peak memory. ``dlse`` [B, T, H] is the cotangent of the LSE output
    (None when the caller only differentiates the attention output):
    dS picks up an extra +P·dLSE term, folded into delta' by
    ``flash_dq``."""
    B, _, H, D = q.shape
    grads = _backward_calls(
        *(_operand(x) for x in (q, k, v, g, out)), lse, D, _layout(D),
        dlse=None if dlse is None else _to_lanes(dlse), **opts)
    return tuple(_from_operand(x, B, H) for x in grads)


def _split_projection(qkv, heads):
    """The head-major fused projection [B, T, heads·3·D] as q, k, v
    [B, T, heads, D] (strided slices: XLA copies them)."""
    B, T, C3 = qkv.shape
    qkv = qkv.reshape(B, T, heads, 3, C3 // (3 * heads))
    return qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]


def _projection_forward(qkv, heads, **opts):
    """The fused projection [B, T, heads·3·D] read where it lies.
    Returns (out [B, T, heads·D], lse [B·H, T, LANES])."""
    B, T, C3 = qkv.shape
    D = C3 // (3 * heads)
    if D % LANES:  # no lane-aligned column block: the transposed operands
        out, lse = _flash_forward(*_split_projection(qkv, heads), **opts)
        return out.reshape(B, T, heads * D), lse
    q, k, v = (_Operand(qkv, heads, 3, i) for i in range(3))
    return _forward_call(q, k, v, D, "projection", **opts)


def _projection_backward(qkv, heads, out, lse, g, **opts):
    """VJP of ``_projection_forward``: the projection's cotangent
    [B, T, heads·3·D], written by the kernels as one array."""
    B, T, C3 = qkv.shape
    D = C3 // (3 * heads)
    if D % LANES:
        rows = (B, T, heads, D)
        grads = _flash_backward(
            *_split_projection(qkv, heads), out.reshape(rows), lse,
            g.reshape(rows), **opts)
        return jnp.stack(grads, axis=3).reshape(B, T, C3)
    q, k, v = (_Operand(qkv, heads, 3, i) for i in range(3))
    return _backward_calls(
        q, k, v, _Operand(g, heads), _Operand(out, heads), lse, D,
        "projection", joined=True, **opts)


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


def _opts(causal, block_q, block_k, interpret):
    return dict(
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret)


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
    return _fa_fwd(q, k, v, causal, block_q, block_k, interpret)[0]


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(
        q, k, v, **_opts(causal, block_q, block_k, interpret))
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, residuals, g):
    return _flash_backward(
        *residuals, g, **_opts(causal, block_q, block_k, interpret))


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def flash_attention_projection(
    qkv,
    heads: int,
    causal: bool = False,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
):
    """``flash_attention`` of a fused, head-major projection: ``qkv``
    [B, T, heads·3·D] with columns ordered [head, (q|k|v), D], as
    ``models/vit.py::MultiHeadAttention``'s ``qkv`` matmul writes it.
    Returns [B, T, heads·D], what the ``proj`` matmul reads.

    Where D is whole 128-lane groups nothing is sliced, transposed or
    stacked on the way: the kernels read a head's q, k and v column
    blocks out of ``qkv`` itself and the backward kernels write its
    cotangent as one array. A narrower head takes the transposed
    operands of the separate entry, sliced here.
    """
    return _fap_fwd(qkv, heads, causal, block_q, block_k, interpret)[0]


def _fap_fwd(qkv, heads, causal, block_q, block_k, interpret):
    out, lse = _projection_forward(
        qkv, heads, **_opts(causal, block_q, block_k, interpret))
    return out, (qkv, out, lse)


def _fap_bwd(heads, causal, block_q, block_k, interpret, residuals, g):
    qkv, out, lse = residuals
    return (_projection_backward(
        qkv, heads, out, lse, g,
        **_opts(causal, block_q, block_k, interpret)),)


flash_attention_projection.defvjp(_fap_fwd, _fap_bwd)


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
    both outputs. The backward reads the LSE as ``flash_fwd`` wrote it,
    not this output: where a caller drops it, nothing is made of it.
    """
    return _fal_fwd(q, k, v, causal, block_q, block_k, interpret)[0]


def _fal_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(
        q, k, v, **_opts(causal, block_q, block_k, interpret))
    return (out, _lse_rows(lse, q.shape[0])), (q, k, v, out, lse)


def _fal_bwd(causal, block_q, block_k, interpret, residuals, cotangents):
    g, dlse = cotangents
    return _flash_backward(
        *residuals, g, dlse, **_opts(causal, block_q, block_k, interpret))


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
