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
there the fusion lives in cuDNN/ATen; here it is explicit Pallas
kernels.

Differentiation is flash end to end: the forward kernel also emits the
per-row log-sum-exp (LSE), and the backward recomputes P = exp(S − LSE)
blockwise from the saved residuals. Peak memory of the whole VJP is
O(T·D); the round-1 version recomputed backward through a dense O(T²)
reference (VERDICT.md "What's missing" #1).

The backward is ONE algorithm in two forms, chosen from what the code
can observe, the shapes (``_backward_form``; no knob, no environment
variable, no model's name). The arithmetic of a block pair — scores,
the class's mask, P, dP = dO·Vᵀ, dS = P ∘ (dP − delta') — exists once,
``_pair``, and both forms call it.

- **resident** (PR 39; ``_resident_kernel``, named ``flash_dkv``):
  where a head's operands, outputs and accumulators fit the VMEM
  budget and its live pairs unroll — the train cells' 2048 x 128, up to
  4096 in bfloat16 — ONE ``pallas_call`` on a grid of (batch·head)
  alone. The pipeline fetches the head's q, k, v, dO, O and LSE rows
  once, the next head's under this head's matmuls; the kernel walks
  the live pairs itself, keys outer, queries inner, computes S, P, dP
  and dS once a pair and accumulates dV, dK and dQ from them: five
  matmuls and one ``exp`` pass a pair. dQ's float32 accumulator for the
  whole head stays in VMEM scratch from the head's first pair to its
  last; delta' = rowsum(dO ∘ O) − dLSE is made once a head inside the
  kernel and never leaves VMEM. The walk is unrolled from the static
  classes: no grid step, no flags table, no ``pl.when``, no strided
  block fetch a pair. ``vmem_limit_bytes`` is reckoned from the shapes.
- **grid** (``flash_dq`` + ``flash_dkv``): a longer head (a ring hop at
  32k). Two kernels, each on a (batch·head, live block pair) grid: one
  by q block producing dQ, one by k block producing dK/dV, each
  recomputing S, P and dP for itself: seven matmuls and two ``exp``
  passes a pair, a grid step a pair in each, and delta' travels from
  the first to the second through HBM.

Every mask (none, causal, block-causal), the ``dlse`` cotangent,
``T != S`` and all three operand layouts go through either form; none
is left to the grid form alone.

Causal masking skips arithmetic, grid steps and fetches (PR 31). Every
(q block, k block) pair is classified from the static shapes
(``_classify``: *interior*, *diagonal* or *dead*, by the block corners
under the same ``_last_key`` the mask applies). The grid's second
dimension runs over the LIVE pairs only, through scalar-prefetched
(outer, inner, flags) tables (``_live_pairs``): a strictly-future pair
is no grid step, so its blocks are never fetched — 10 steps of 16 at
2048 x 2048 in blocks of 512, in the forward and the grid backward;
the resident backward walks the same 10 pairs inside its one step
(``_walk``). Of the live pairs
only the diagonal ones run the program with the mask (iota, compare,
select); interior pairs run the plain one, and the guards against a
row with no visible key are built only where such a row can exist
(``T > S``). Each traced ``pallas_call`` leaves a ``flash.plan`` record
in the tracer's ring (``obs/tracer.py``): blocks, pairs visited, of
which masked, of which dead, the kernel's form (``grid`` or
``resident``), its grid steps a (batch·head), the matmuls a pair costs
in it (2 forward; 5 resident, 3 + 4 over the grid pair) and the VMEM
bytes reckoned.

What a step does NOT pay for, found on the chip (PERF.md section 6, PR
31): a cross-lane sum (the forward's ``l`` travels as lane-partial
sums, reduced once a q block), lane broadcasts of [block, 1] columns
(the forward's statistics stay [block_q, LANES]), and transposes of
the [block_q, block_k] tiles on their way to the MXU (the dK/dV walk,
grid or resident, builds its tile keys first). MXU operands are float32 copies of the
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
still runs over (batch, head) — ``_spec`` is the one index rule of the
grid kernels, ``_head_spec`` the same rule for a head's whole columns
(the resident backward). Two entries share them. ``flash_attention_projection``
takes the fused head-major projection [B, T, H·3·D] as
``models/vit.py``'s ``qkv`` matmul wrote it (one array seen three
times, stride 3, offsets 0, 1, 2), writes ``out`` as [B, T, H·D], which
is what ``proj`` reads, and its backward writes dq, dk, dv into ONE
[B, T, H·3·D] array, the projection's cotangent (resident: a head's
[T, 3·D] dq | dk | dv columns are the kernel's one output block; grid:
``flash_dq`` writes its column blocks of it, ``flash_dkv`` takes it
aliased and writes a head's dk | dv beside them). ``flash_attention`` /
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
``flash_fwd`` wrote it. In the grid form delta' = rowsum(dO ∘ O) − dLSE
is made by ``flash_dq`` on the first step of each q block, from the dO
and O blocks it holds, and handed to ``flash_dkv`` as such an array;
the resident kernel makes it once a head and keeps the head's LSE and
delta' in scratch as its tiles meet them (a [1, T] row keys first).
The grid kernels' scratch
accumulators persist across the steps of an output block and flush on
its last one (``pl.when`` on the step's flags), the scheme of
jax.experimental.pallas.ops.tpu.flash_attention. The live-pair tables
sit in scalar memory, 12 bytes a pair: T/block_q x S/block_k entries at
most (4,096 at 32k x 32k in blocks of 512).

``interpret=True`` runs the kernels on CPU for tests — the same
program the TPU compiles, minus Mosaic. On-chip agreement with the
dense reference is checked by ``scripts/check_kernels.py`` (run by
``chip_smoke.py``; both forms of the backward through both entries;
``--time`` prints the forward's and each form's backward's device ms a
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


def _pair(q, k, v, do, lse, dl, q_start, k_start, *, masked, keys_first,
          causal, block_q, block_k, T_total, S_total):
    """THE arithmetic of one live (q block, k block) pair of the
    backward, the one form all three backward kernels run: the scores
    recomputed from ``q`` (which carries the softmax scale), the mask
    of a diagonal pair, P = exp(S − LSE) from the forward's saved LSE,
    dP = dO·Vᵀ and dS = P ∘ (dP − delta'). Returns (P, dS) as
    [block_q, block_k] tiles, or KEYS FIRST, [block_k, block_q]: then
    Pᵀ and dSᵀ enter the dK/dV matmuls as they stand (queries first,
    each would be transposed on its way to the MXU) and ``lse`` and
    ``dl`` are [1, block_q] rows that meet the tile along the sublanes,
    where queries first takes [block_q, 1] columns."""
    swap = (lambda a, b: (b, a)) if keys_first else (lambda a, b: (a, b))
    s = _dot(*swap(q, k), (1, 1))
    if masked:
        s = _causal_mask(
            s, q_start, k_start, block_q, block_k, S_total, T_total,
            causal, keys_first,
        )
    # masked: exp(-inf) = 0
    empty_rows = bool(causal) and T_total > S_total
    p = jnp.exp(s - _saved_lse(lse, empty_rows))
    dp = _dot(*swap(do, v), (1, 1))
    return p, p * (dp - dl)


def _dq_kernel(
    qi_ref, kj_ref, flags_ref, q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
    *rest, scale, **geometry,
):
    """Grid (B·H, live pairs by q block): dQ accumulates over the
    streamed KV blocks of each q block — with ``_dkv_kernel`` the GRID
    form of the backward, for a head that does not fit the VMEM
    (``_backward_form``).

    The first step of a q block also makes its rows of delta' =
    rowsum(dO ∘ O) − dLSE from the dO and O blocks it holds, lane-
    broadcast into ``dl_ref``: an OUTPUT ([B·H, T, LANES], what
    ``flash_dkv`` reads) that this kernel reads back on every step of
    the block. ``rest`` is ``dq_ref, dl_ref`` and the accumulator, led
    by ``dlse_ref`` (lane-broadcast like the LSE) where the caller
    differentiates the LSE output too. dQ = scale · dS·K (``_pair``).
    """
    *dlse_ref, dq_ref, dl_ref, dq_acc = rest
    block_q, block_k = geometry["block_q"], geometry["block_k"]
    step = pl.program_id(1)
    flags = flags_ref[step]
    q_start, k_start = qi_ref[step] * block_q, kj_ref[step] * block_k

    @pl.when(flags & _FIRST != 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dl_ref[0] = _delta(do_ref[0], o_ref[0], *(r[0] for r in dlse_ref))

    def _compute(masked):
        q = q_ref[0].astype(jnp.float32) * scale
        _, ds = _pair(
            q, k_ref[0], v_ref[0], do_ref[0], _row_stat(lse_ref),
            _row_stat(dl_ref), q_start, k_start, masked=masked,
            keys_first=False, **geometry)
        dq_acc[...] = dq_acc[...] + _dot(ds, k_ref[0], (1, 0))

    _by_class(flags, geometry["causal"], _compute)

    @pl.when(flags & _LAST != 0)
    def _flush():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _delta(do, o, dlse=None):
    """delta' = rowsum(dO ∘ O) − dLSE of a block's rows, float32,
    broadcast over [rows, LANES] (``dlse`` lies so already)."""
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        axis=-1, keepdims=True)
    dl = jnp.broadcast_to(delta, (delta.shape[0], LANES))
    return dl if dlse is None else dl - dlse


def _dkv_kernel(
    kj_ref, qi_ref, flags_ref, k_ref, v_ref, q_ref, do_ref, lse_ref, dl_ref,
    *rest, scale, joined=False, **geometry,
):
    """Grid (B·H, live pairs by k block): dK/dV accumulate over the
    streamed Q blocks of each k block (the grid form's second kernel).

    ``rest`` is ``dk_ref, dv_ref`` and the two accumulators, or, when
    ``joined`` (the fused projection), the cotangent array itself
    (aliased to the output, in no memory the kernel reads), ``dkv_ref``
    and the accumulators: the flush then writes the head's
    [block_k, 2·D] dk | dv columns of the projection's cotangent in
    one block.

    Where the q block fills whole lane groups the tile is built KEYS
    FIRST (``_pair``)."""
    block_q, block_k = geometry["block_q"], geometry["block_k"]
    step = pl.program_id(1)
    flags = flags_ref[step]
    q_start, k_start = qi_ref[step] * block_q, kj_ref[step] * block_k
    keys_first = block_q % LANES == 0
    *out_refs, dk_acc, dv_acc = rest

    @pl.when(flags & _FIRST != 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute(masked):
        # q carries the scale, and so does the dK it accumulates into
        q = q_ref[0].astype(jnp.float32) * scale
        # [1, block_q] rows keys first, else [block_q, 1] columns
        stat = (lambda ref: ref[0].T[:1]) if keys_first else _row_stat
        p, ds = _pair(
            q, k_ref[0], v_ref[0], do_ref[0], stat(lse_ref), stat(dl_ref),
            q_start, k_start, masked=masked, keys_first=keys_first,
            **geometry)
        over_queries = int(keys_first)
        dv_acc[...] = dv_acc[...] + _dot(p, do_ref[0], (over_queries, 0))
        dk_acc[...] = dk_acc[...] + _dot(ds, q, (over_queries, 0))

    _by_class(flags, geometry["causal"], _compute)

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


def _resident_kernel(
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest,
    scale, walk, joined=False, **geometry,
):
    """Grid (B·H,): the whole backward of one head, its operands
    RESIDENT in VMEM (the resident form, ``_backward_form``). The
    pipeline fetches a head's q, k, v, dO, O and LSE rows once, the next
    head's under this head's matmuls, and the kernel walks the head's
    live block pairs itself, keys outer, queries inner, both ascending
    (``_dkv_kernel``'s walk): per pair ``_pair`` once, then dV += Pᵀ·dO,
    dK += dSᵀ·Q and dQ += dS·K: FIVE matmuls and one ``exp`` pass where
    the grid form's two kernels run seven and two. No grid step, flags
    table or strided block fetch is paid a pair, and delta' is made
    once a head and never leaves VMEM.

    ``walk`` (``_walk``) is static, an entry a k block: the first live q
    block and the first interior one. The walk is UNROLLED: every
    pair's class and every slice is static (no ``pl.when``, no table),
    and the compiler schedules across pairs (on the chip 1.17 ms a call
    at the train cells' shape against 1.53 for the same walk as two
    loops: PERF.md section 6, PR 39). dQ's float32 accumulator for the
    whole head stays in scratch from the head's first pair to its last
    and takes the k blocks in ascending order.

    ``rest``: ``dlse_ref`` where the LSE output is differentiated, the
    outputs — ``dq, dk, dv``, or with ``joined`` the head's
    [T, 3·D] dq | dk | dv columns of the projection's cotangent as ONE
    block — and the scratch: dQ's accumulator [T, D] and the head's LSE
    and delta' as the tiles meet them (``_pair``: a [1, T] row keys
    first, else a [T, 1] column)."""
    *refs, dq_acc, lse_s, dl_s = rest
    outs = refs[-1:] if joined else refs[-3:]
    dlse_ref = refs[:-len(outs)]
    block_q, block_k = geometry["block_q"], geometry["block_k"]
    n_q = geometry["T_total"] // block_q
    D = dq_acc.shape[1]
    keys_first = block_q % LANES == 0
    over_queries = int(keys_first)

    def put(which, at, x):  # 0, 1, 2: dq, dk, dv
        if joined:
            outs[0][0, at, which * D:(which + 1) * D] = x.astype(
                outs[0].dtype)
        else:
            outs[which][0, at] = x.astype(outs[which].dtype)

    def stat_at(at):  # a q block's place in the head's statistics
        return (slice(None), at) if keys_first else (at, slice(None))

    def as_met(x):  # [block_q, LANES] lane-broadcast -> as a tile meets it
        return x.T[:1] if keys_first else x[:, :1]

    q_blocks = [pl.ds(i * block_q, block_q) for i in range(n_q)]
    for at in q_blocks:
        dl_s[stat_at(at)] = as_met(_delta(
            do_ref[0, at], o_ref[0, at], *(r[0, at] for r in dlse_ref)))
        lse_s[stat_at(at)] = as_met(lse_ref[0, at])
    dq_acc[...] = jnp.zeros_like(dq_acc)

    for j, (lo, mid) in enumerate(walk):
        at_k = pl.ds(j * block_k, block_k)
        k, v = k_ref[0, at_k], v_ref[0, at_k]
        dk = dv = jnp.zeros((block_k, D), jnp.float32)
        for i in range(lo, n_q):
            at = q_blocks[i]
            # q carries the scale, and so does the dK it accumulates into
            q = q_ref[0, at].astype(jnp.float32) * scale
            do = do_ref[0, at]
            p, ds = _pair(
                q, k, v, do, lse_s[stat_at(at)], dl_s[stat_at(at)],
                i * block_q, j * block_k, masked=i < mid,
                keys_first=keys_first, **geometry)
            dv = dv + _dot(p, do, (over_queries, 0))
            dk = dk + _dot(ds, q, (over_queries, 0))
            dq_acc[at] = dq_acc[at] + _dot(ds, k, (1 - over_queries, 0))
        put(1, at_k, dk)
        put(2, at_k, dv)

    for at in q_blocks:
        put(0, at, dq_acc[at] * scale)


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


def _record_plan(kernel, block_q, block_k, classes, layout, form, steps,
                 matmuls, vmem_bytes=0):
    """One ``pallas_call``'s ``flash.plan`` record in the tracer's ring
    (trace time: a compiled step leaves none): the block pairs a
    (batch·head) visits (``classes``, one each), how many of them run
    the masked program, how many are dead, the dtype the MXU's operands
    are handed over in, where the operands lie (``projection``, ``heads_last`` or
    ``transposed``), the kernel's form (``grid``: a grid step a pair;
    ``resident``: the head in VMEM, the kernel walks the pairs), the
    grid steps a (batch·head) takes, the matmuls a pair costs in this
    kernel (forward 2; backward 5 resident, 3 + 4 over the grid form's
    two kernels) and the VMEM bytes reckoned for it (0: the compiler's
    default limit stands)."""
    get_tracer().complete(
        "flash.plan", time.perf_counter(), 0.0,
        nums=(kernel, block_q, block_k, len(classes),
              sum(1 for c in classes if c == _DIAGONAL),
              sum(1 for c in classes if c == _DEAD), "float32", layout,
              form, steps, matmuls, vmem_bytes),
    )


def _plan(kernel, T, S, block_q, block_k, causal, layout, matmuls, *,
          by_key=False):
    """The live-pair tables of one grid ``pallas_call``, and its
    ``flash.plan`` record."""
    tables = _live_pairs(
        _classify(T, S, block_q, block_k, causal), by_key=by_key)
    flags = tables[2]
    _record_plan(
        kernel, block_q, block_k,
        [f & (_DIAGONAL | _DEAD) for f in flags], layout, "grid",
        len(flags), matmuls)
    return tuple(jnp.asarray(t, jnp.int32) for t in tables)


def _walk(classes):
    """The resident kernel's walk of ``classes`` (``_classify``), an
    entry a k block: ``(lo, mid)``, the first live q block and the
    first interior one. Later rows see more keys, so down a k block's
    column the pairs are dead, then diagonal, then interior: q blocks
    ``lo`` to ``mid`` run the masked program, ``mid`` on the plain
    one."""
    walk = []
    for column in zip(*classes):
        dead = sum(1 for c in column if c == _DEAD)
        diagonal = sum(1 for c in column if c == _DIAGONAL)
        assert list(column) == (
            [_DEAD] * dead + [_DIAGONAL] * diagonal
            + [0] * (len(column) - dead - diagonal)), column
        walk.append((dead, dead + diagonal))
    return tuple(walk)


def _forward_call(
    q, k, v, D, layout, *, causal, block_q: int, block_k: int, interpret
):
    """``flash_fwd`` on ``_Operand``s. Returns (out [B', T, heads·D] in
    the layout of a stride-1 operand, lse [B·H, T, LANES] fp32)."""
    Bp, T, _ = q.array.shape
    S = k.array.shape[1]
    heads = q.heads
    block_q, block_k = _pick_blocks(T, S, block_q, block_k, q.array.dtype)
    by_q = _plan("flash_fwd", T, S, block_q, block_k, causal, layout, 2)
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


# What the resident backward may ask of the VMEM: three quarters of
# the 128 MiB a TensorCore of a TPU v4, v5e, v5p or v6e holds. The rest
# is the compiler's.
_VMEM_BUDGET = 96 * 1024 * 1024
# The live block pairs its unrolled walk may hold: a non-causal head of
# 4096 in blocks of 512. The program grows with the pairs and Mosaic's
# time to compile it faster (for a described v5e, causal in blocks of
# 512: 10 pairs at 2048 4 s, 36 at 4096 19 s, 136 at 8192 98 s).
_UNROLL_PAIRS = 64


def _backward_form(T, S, D, dtype, block_q, block_k, causal, *, dlse=False,
                   budget=_VMEM_BUDGET):
    """How the backward of one (batch·head) of ``T`` queries and ``S``
    keys runs, from what the code can observe, its shapes: -> (form,
    VMEM bytes reckoned). ``resident`` (``_resident_kernel``) where the
    head's operands, outputs and accumulators fit ``budget`` and its
    live block pairs unroll (``_UNROLL_PAIRS``), else ``grid``
    (``flash_dq`` + ``flash_dkv``, a block pair a grid step: a ring hop
    at 32k). Reckoned: q, dO, O [T, D], k, v [S, D] and the LSE rows
    [T, LANES] float32 (and dLSE), twice, because the pipeline fetches
    the next head under this one; dq, dk, dv, twice; dQ's float32
    accumulator [T, D], a k block's dK and dV, the head's statistics;
    and the float32 tiles of a pair — scores, P, dP, dS and the
    compiler's copies of them [block_q, block_k], the blocks'
    [block, D] copies — half again on top for what the compiler keeps
    of its own. A minor dimension under 128 lanes is padded to 128."""
    block_q, block_k = _pick_blocks(T, S, block_q, block_k, dtype)
    item = jnp.dtype(dtype).itemsize
    lanes = -(-D // LANES) * LANES
    operands = (3 * T + 2 * S) * lanes * item + (1 + dlse) * T * LANES * 4
    outputs = (T + 2 * S) * lanes * item
    scratch = (T + 2 * block_k) * lanes * 4 + 2 * T * 4 * (
        LANES if block_q % LANES else 8)
    tiles = 6 * block_q * block_k * 4 + 6 * max(block_q, block_k) * lanes * 4
    reckoned = (2 * operands + 2 * outputs + scratch + tiles) * 3 // 2
    pairs = sum(c != _DEAD for row in _classify(T, S, block_q, block_k, causal)
                for c in row)
    fits = reckoned <= budget and pairs <= _UNROLL_PAIRS
    return ("resident" if fits else "grid"), reckoned


def _backward_calls(
    q, k, v, g, out, lse, D, layout, *, dlse=None, **opts,
):
    """The backward on ``_Operand``s, in the form ``_backward_form``
    finds for the shapes: ONE kernel over a resident head
    (``_backward_resident``), or the grid pair (``_backward_grid``).
    One algorithm, ``_pair``, under both; every mask, ``dlse``,
    ``T != S`` and all three layouts go through either.

    ``out`` is the forward's, ``lse`` [B·H, T, LANES] fp32 as it wrote
    it, ``dlse`` the LSE output's cotangent in the same layout or None.
    Returns (dq, dk, dv), each like a stride-1 operand, or with
    ``joined`` ONE [B', T, heads·3·D] array, the fused projection's
    cotangent."""
    form, _ = _backward_form(
        q.array.shape[1], k.array.shape[1], D, q.array.dtype,
        opts["block_q"], opts["block_k"], opts["causal"],
        dlse=dlse is not None)
    call = _backward_resident if form == "resident" else _backward_grid
    return call(q, k, v, g, out, lse, D, layout, dlse=dlse, **opts)


def _head_spec(rows, width, heads=1, stride=1, offset=0):
    """BlockSpec of a head's WHOLE [rows, width] columns of a
    [B', rows, columns] array under ``_spec``'s rule for the head: the
    resident kernel's operands, on a grid of (batch·head) alone."""

    def index(bh):
        return (lax.div(bh, heads), 0, lax.rem(bh, heads) * stride + offset)

    return pl.BlockSpec((1, rows, width), index, memory_space=pltpu.VMEM)


def _backward_resident(
    q, k, v, g, out, lse, D, layout, *, causal, block_q, block_k, interpret,
    dlse=None, joined: bool = False,
):
    """``flash_dkv``, the resident form: one call, one grid step a
    (batch·head) (``_resident_kernel``; it keeps the name of the kernel
    whose walk it is, which now also accumulates dQ). With ``joined``
    its output block is a head's [T, 3·D] dq | dk | dv columns of the
    projection's cotangent: no second call, no aliasing. The call
    itself is a jitted function of its shapes (``_resident_call``): a
    model's layers are ONE program, traced and lowered once and not
    once a layer (its unrolled walk is a longer trace than a grid
    kernel's two bodies)."""
    T, S = q.array.shape[1], k.array.shape[1]
    dtype = q.array.dtype
    _, vmem_bytes = _backward_form(
        T, S, D, dtype, block_q, block_k, causal, dlse=dlse is not None)
    block_q, block_k = _pick_blocks(T, S, block_q, block_k, dtype)
    classes = _classify(T, S, block_q, block_k, causal)
    _record_plan(
        "flash_dkv", block_q, block_k,
        [c for row in classes for c in row if c != _DEAD], layout,
        "resident", 1, 5, vmem_bytes)
    operands = (q, k, v, g, out)
    return _resident_call(
        *(x.array for x in operands), lse, *(() if dlse is None else (dlse,)),
        rules=tuple(tuple(x[1:]) for x in operands), D=D, causal=causal,
        walk=_walk(classes), block_q=block_q, block_k=block_k,
        interpret=interpret, joined=joined, vmem_bytes=vmem_bytes)


@functools.partial(jax.jit, static_argnames=(
    "rules", "D", "causal", "walk", "block_q", "block_k", "interpret",
    "joined", "vmem_bytes"))
def _resident_call(q, k, v, g, out, *stats, rules, D, causal, walk, block_q,
                   block_k, interpret, joined, vmem_bytes):
    """The resident backward's ``pallas_call`` on the operands' arrays;
    ``rules`` are their (heads, stride, offset) (``_Operand``), ``stats``
    the LSE rows and, where given, the dLSE rows, ``walk`` the head's
    (``_walk``)."""
    Bp, T, _ = q.shape
    S = k.shape[1]
    heads = rules[0][0]
    if joined:
        out_specs = _head_spec(T, 3 * D, heads)
        out_shape = jax.ShapeDtypeStruct((Bp, T, heads * 3 * D), q.dtype)
    else:
        out_specs = [_head_spec(n, D, heads) for n in (T, S, S)]
        out_shape = [jax.ShapeDtypeStruct((Bp, n, heads * D), q.dtype)
                     for n in (T, S, S)]
    # a row keys first (``_pair``), else a column
    stat_shape = (1, T) if block_q % LANES == 0 else (T, 1)
    return pl.pallas_call(
        functools.partial(
            _resident_kernel, scale=D**-0.5, joined=joined, causal=causal,
            walk=walk, block_q=block_q, block_k=block_k, T_total=T,
            S_total=S),
        grid=(Bp * heads,),
        in_specs=[
            *(_head_spec(n, D, *rule)
              for n, rule in zip((T, S, S, T, T), rules)),
            *(_head_spec(T, LANES) for _ in stats),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            _scratch((T, D)), _scratch(stat_shape), _scratch(stat_shape)],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, g, out, *stats)


def _backward_grid(
    q, k, v, g, out, lse, D, layout, *, causal, block_q, block_k, interpret,
    dlse=None, joined: bool = False,
):
    """``flash_dq`` and ``flash_dkv``, the grid form: a live block pair
    a grid step in each, 3 + 4 matmuls a pair between them. With
    ``joined`` ``flash_dq`` writes a head's dq columns of the
    projection's cotangent, ``flash_dkv`` the dk and dv columns of the
    same buffer. delta' travels from ``flash_dq``, which makes it, to
    ``flash_dkv`` as [B·H, T, LANES] fp32."""
    Bp, T, _ = q.array.shape
    S = k.array.shape[1]
    heads = q.heads
    dtype = q.array.dtype
    block_q, block_k = _pick_blocks(T, S, block_q, block_k, dtype)
    by_q = _plan("flash_dq", T, S, block_q, block_k, causal, layout, 3)
    by_k = _plan(
        "flash_dkv", T, S, block_q, block_k, causal, layout, 4, by_key=True)
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
