"""Routed experts without drops: rows sorted by expert, one grouped
matmul per projection.

The serving shape of a sparse MLP (``models/sdar.py``): N tokens a
step (the lanes' block positions, or one prefill chunk), each sent to
its ``top_k`` of E experts, every assignment kept. Running every
expert on every token and multiplying by a mostly-zero combine
(``models/generate._moe_mlp``, fine at 4 experts) costs E / top_k
times the needed work at 128 experts; here an expert touches only the
rows routed to it:

- :func:`route` — softmax over all E in fp32, the ``top_k`` largest,
  optionally renormalised (the published layer's ``norm_topk_prob``);
  or, ``scoring="sigmoid"``, each expert's own sigmoid, the choice made
  on score + ``bias`` and the weight on the score alone, renormalised
  over the chosen and scaled.
- :func:`group_rows` — the N x top_k assignments sorted by expert and
  laid out in row TILES of ``TILE_ROWS``: every expert's group is padded to a
  whole number of tiles, so a tile belongs to exactly one expert. The
  padded length is static (worst case every group one row past a
  tile); the tiles past the last group are marked dead.
- :func:`grouped_matmul_gate_up` / :func:`grouped_matmul_down` — Pallas
  TPU kernels on a ``(tiles,)`` grid. The tile -> expert map rides
  scalar prefetch; the weight index maps pick the tile's expert, and
  Pallas fetches a block only when its index changes, so an expert's
  ``[d, f]`` matrices cross HBM once however many tiles it owns and a
  dead tile (same expert as the last live one, compute skipped) costs
  nothing. Operands in the weights' dtype (bfloat16 as stored), fp32
  accumulation. The trace names them ``moe_grouped_gate_up`` and
  ``moe_grouped_down``.
- the plain ``jnp`` path (``impl="jnp"``, what ``auto`` picks off the
  TPU): the same tiles, each against its expert's gathered matrix in
  one einsum.
- :func:`moe_layer` — route, group, gate/up (SiLU(gate) * up), down,
  and the weighted sum of each token's ``top_k`` rows; also returns the
  step's routing counts (rows routed, the fullest expert's rows,
  experts hit) for the engine's counters.
- :func:`moe_share_layer` — the same for a process that holds experts
  ``first .. first + E_held - 1`` of the ``E`` the router scores (expert
  parallelism, one member's part): the assignments to experts held
  elsewhere are sorted behind the held ones into tiles marked dead, so
  they cost neither a fetch nor a matmul, and add nothing. What the
  other members would add is theirs to add; nothing here stands in for
  them. At widths whose gate and up matrices do not fit VMEM twice
  over, the gate/up kernel walks the tiles once a COLUMN block of the
  expert width (columns outermost, so a matrix block still crosses
  HBM once an expert).

Forward only: serving never differentiates it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows a tile holds. bfloat16 rows tile 16 sublanes at a time; at 8
# routed rows an expert on average (128 lanes' positions x top-8 over
# 128 experts) a larger tile only adds padding (32 read slower on the
# chip, PERF.md section 6).
TILE_ROWS = 16
# Two [d, f] matrices double-buffered pass the default scoped limit at
# the published widths (2048 x 768 bf16 = 3 MB each).
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def route(logits, top_k: int, normalize: bool = True, *,
          scoring: str = "softmax", bias=None, scale: float = 1.0):
    """Router logits ``[N, E]`` -> (experts ``[N, top_k]`` int32,
    weights ``[N, top_k]`` fp32): softmax over ALL experts in fp32,
    the ``top_k`` largest (ties to the lower index), renormalised to
    sum to one when ``normalize``.

    ``scoring="sigmoid"``: the score is each expert's own sigmoid; the
    ``top_k`` are chosen by score + ``bias`` (``[E]``, the balancing
    bias: it moves the CHOICE and never the weight), the weights are
    the chosen experts' scores, renormalised when ``normalize``, times
    ``scale``."""
    if scoring == "softmax":
        p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        w, idx = lax.top_k(p, top_k)
    elif scoring == "sigmoid":
        p = jax.nn.sigmoid(logits.astype(jnp.float32))
        chosen = p if bias is None else p + bias.astype(jnp.float32)
        _, idx = lax.top_k(chosen, top_k)
        w = jnp.take_along_axis(p, idx, axis=-1)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    if normalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if scoring == "sigmoid":
        w = w * scale
    return idx.astype(jnp.int32), w


class Grouped(NamedTuple):
    """The sorted, tile-padded layout of one step's assignments."""

    row_token: jax.Array  # [M] int32: token of each padded row; N = none
    dest: jax.Array  # [N, top_k] int32: padded row of each assignment
    tile_expert: jax.Array  # [M / TILE_ROWS] int32: the expert a tile belongs to
    live_tiles: jax.Array  # [1] int32: tiles that hold rows
    counts: jax.Array  # [E] int32: rows routed to each expert


def padded_rows(assignments: int, num_experts: int) -> int:
    """Static length of the padded layout: every non-empty group may
    end one row past a tile boundary."""
    tm = TILE_ROWS
    worst = assignments + min(num_experts, assignments) * (tm - 1)
    return -(-worst // tm) * tm


def group_rows(idx, num_experts: int) -> Grouped:
    """Sort the ``[N, top_k]`` expert choices by expert and pad each
    expert's group to whole tiles of ``TILE_ROWS``. No assignment is
    dropped: the layout's length is its worst case."""
    N, k = idx.shape
    A, E, tm = N * k, num_experts, TILE_ROWS
    M = padded_rows(A, E)
    flat = idx.reshape(A)
    counts = jnp.zeros((E,), jnp.int32).at[flat].add(1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sorted_e = flat[order]
    padded = (counts + tm - 1) // tm * tm
    pad_end = jnp.cumsum(padded)
    start = jnp.cumsum(counts) - counts
    dest_sorted = (
        (pad_end - padded)[sorted_e]
        + jnp.arange(A, dtype=jnp.int32) - start[sorted_e]
    )
    row_token = jnp.full((M,), N, jnp.int32).at[dest_sorted].set(
        order // k, unique_indices=True
    )
    dest = jnp.zeros((A,), jnp.int32).at[order].set(
        dest_sorted, unique_indices=True
    )
    live = (pad_end[-1] // tm).astype(jnp.int32)
    tiles = jnp.arange(M // tm, dtype=jnp.int32)
    owner = jnp.searchsorted(pad_end, tiles * tm, side="right")
    owner = jnp.minimum(owner, E - 1).astype(jnp.int32)
    # A dead tile repeats the last live tile's expert: no new fetch.
    last = owner[jnp.maximum(live - 1, 0)]
    return Grouped(
        row_token=row_token,
        dest=dest.reshape(N, k),
        tile_expert=jnp.where(tiles < live, owner, last),
        live_tiles=live[None],
        counts=counts,
    )


# ---- the kernels ------------------------------------------------------


def _silu(g):
    return g * jax.nn.sigmoid(g)


def _gate_up_kernel(te_ref, live_ref, x_ref, wg_ref, wu_ref, h_ref, *,
                    tile_axis: int = 0):
    del te_ref

    @pl.when(pl.program_id(tile_axis) < live_ref[0])
    def _():
        x = x_ref[...].astype(wg_ref.dtype)
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h_ref[...] = (_silu(g) * u).astype(h_ref.dtype)


def _down_kernel(te_ref, live_ref, h_ref, wd_ref, y_ref):
    del te_ref

    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        h = h_ref[...].astype(wd_ref.dtype)
        y_ref[...] = jnp.dot(
            h, wd_ref[0], preferred_element_type=jnp.float32
        ).astype(y_ref.dtype)


def column_block(d: int, f: int, itemsize: int, matrices: int = 2) -> int:
    """Columns of the expert width a grouped call takes at a time: all
    ``f`` where ``matrices`` ``[d, f]`` blocks fit VMEM double-buffered
    under the limit with room for the rows (the accepted shapes), else
    the largest halving of ``f`` (a multiple of 128) that does."""
    budget = _VMEM_LIMIT_BYTES * 3 // 4
    fb = f
    while (2 * matrices * d * fb * itemsize > budget and fb % 256 == 0):
        fb //= 2
    return fb


def _grouped_columns_call(kernel, name, rows, weights, g: Grouped, fb,
                          out_dtype, interpret):
    """The gate/up call a column block at a time: grid (f / fb, tiles),
    columns OUTERMOST, so within one column block the weight index
    changes only where the expert does and each ``[d, fb]`` block
    crosses HBM once; the rows cross once a column block."""
    M, K = rows.shape
    tm = TILE_ROWS
    f = weights[0].shape[-1]
    vmem = {"memory_space": pltpu.VMEM}
    row_spec = pl.BlockSpec((tm, K), lambda c, t, te, live: (t, 0), **vmem)
    w_specs = [
        pl.BlockSpec((1, K, fb), lambda c, t, te, live: (te[t], 0, c),
                     **vmem)
        for _ in weights
    ]

    return pl.pallas_call(
        functools.partial(kernel, tile_axis=1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(f // fb, M // tm),
            in_specs=[row_spec, *w_specs],
            out_specs=pl.BlockSpec(
                (tm, fb), lambda c, t, te, live: (t, c), **vmem
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((M, f), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=name,
    )(g.tile_expert, g.live_tiles, rows, *weights)


def _grouped_call(kernel, name, rows, weights, g: Grouped, out_cols,
                  out_dtype, interpret):
    M, K = rows.shape
    tm = TILE_ROWS
    if len(weights) == 2:
        fb = column_block(K, out_cols, weights[0].dtype.itemsize)
        if fb < out_cols:
            return _grouped_columns_call(
                kernel, name, rows, weights, g, fb, out_dtype, interpret)
    vmem = {"memory_space": pltpu.VMEM}
    row_spec = pl.BlockSpec((tm, K), lambda t, te, live: (t, 0), **vmem)
    w_specs = [
        pl.BlockSpec((1, *w.shape[1:]), lambda t, te, live: (te[t], 0, 0),
                     **vmem)
        for w in weights
    ]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M // tm,),
            in_specs=[row_spec, *w_specs],
            out_specs=pl.BlockSpec(
                (tm, out_cols), lambda t, te, live: (t, 0), **vmem
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((M, out_cols), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=name,
    )(g.tile_expert, g.live_tiles, rows, *weights)


def _resolve(impl: str) -> tuple[str, bool]:
    """-> (pallas | jnp, interpret)."""
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        impl = "pallas" if on_tpu else "jnp"
    if impl not in ("pallas", "jnp"):
        raise ValueError(
            f"unknown grouped-matmul impl {impl!r}: expected 'auto', "
            "'pallas' or 'jnp'"
        )
    return impl, not on_tpu


def _tiles_einsum(rows, w, g: Grouped):
    """The plain path: each tile against its expert's gathered matrix."""
    M, K = rows.shape
    tm = TILE_ROWS
    out = jnp.einsum(
        "tmk,tkn->tmn", rows.reshape(M // tm, tm, K).astype(w.dtype),
        w[g.tile_expert], preferred_element_type=jnp.float32,
    )
    return out.reshape(M, -1)


def grouped_matmul_gate_up(rows, w_gate, w_up, g: Grouped, *,
                           impl: str = "auto"):
    """``rows`` ``[M, d]`` in the padded layout, ``w_gate``/``w_up``
    ``[E, d, f]`` -> ``SiLU(rows @ gate_e) * (rows @ up_e)`` ``[M, f]``
    in the weights' dtype, each tile under its own expert. Rows of
    dead tiles are not written."""
    impl, interpret = _resolve(impl)
    if impl == "jnp":
        gate = _tiles_einsum(rows, w_gate, g)
        up = _tiles_einsum(rows, w_up, g)
        return (_silu(gate) * up).astype(w_gate.dtype)
    return _grouped_call(
        _gate_up_kernel, "moe_grouped_gate_up", rows, (w_gate, w_up), g,
        w_gate.shape[-1], w_gate.dtype, interpret,
    )


def grouped_matmul_down(rows, w_down, g: Grouped, *, impl: str = "auto"):
    """``rows`` ``[M, f]``, ``w_down`` ``[E, f, d]`` -> ``[M, d]`` fp32."""
    impl, interpret = _resolve(impl)
    if impl == "jnp":
        return _tiles_einsum(rows, w_down, g)
    return _grouped_call(
        _down_kernel, "moe_grouped_down", rows, (w_down,), g,
        w_down.shape[-1], jnp.float32, interpret,
    )


@jax.named_scope("moe")
def moe_layer(x, router_logits, w_gate, w_up, w_down, *, top_k: int,
              normalize: bool = True, impl: str = "auto"):
    """``x`` ``[N, d]`` and its router logits ``[N, E]`` -> (the
    experts' weighted sum ``[N, d]`` fp32, counts ``[3]`` int32: rows
    routed, the fullest expert's rows, experts hit)."""
    N, d = x.shape
    E = w_gate.shape[0]
    idx, w = route(router_logits, top_k, normalize)
    g = group_rows(idx, E)
    # One zero row past the tokens: what a padding row reads.
    src = jnp.concatenate(
        [x.astype(w_gate.dtype), jnp.zeros((1, d), w_gate.dtype)]
    )
    rows = src[g.row_token]
    h = grouped_matmul_gate_up(rows, w_gate, w_up, g, impl=impl)
    y = grouped_matmul_down(h, w_down, g, impl=impl)
    out = jnp.einsum("nkd,nk->nd", y[g.dest], w)
    stats = jnp.stack([
        jnp.int32(N * top_k), g.counts.max(), (g.counts > 0).sum(),
    ]).astype(jnp.int32)
    return out, stats


def hold_share(idx, first: int, held: int, num_experts: int):
    """The routed choices ``[N, top_k]`` over all ``num_experts`` ->
    (the layout of the assignments to experts ``first .. first + held
    - 1``, which assignments those are ``[N, top_k]`` bool). An
    assignment to an expert held elsewhere joins one group sorted
    BEHIND the held ones; its tiles are marked dead, so the kernels
    neither fetch nor compute them, and their rows are never written."""
    if not 0 <= first <= first + held <= num_experts:
        raise ValueError(
            f"experts {first}..{first + held - 1} are not among the "
            f"{num_experts} the router scores")
    local = idx - first
    here = (local >= 0) & (local < held)
    g = group_rows(jnp.where(here, local, held), held + 1)
    tm = TILE_ROWS
    live = (jnp.sum((g.counts[:held] + tm - 1) // tm)).astype(jnp.int32)
    tiles = jnp.arange(g.tile_expert.shape[0], dtype=jnp.int32)
    last = g.tile_expert[jnp.maximum(live - 1, 0)]
    last = jnp.minimum(last, held - 1)
    return g._replace(
        tile_expert=jnp.where(tiles < live, g.tile_expert, last),
        live_tiles=live[None], counts=g.counts[:held],
    ), here


@jax.named_scope("moe_share")
def moe_share_layer(x, router_logits, w_gate, w_up, w_down, *, top_k: int,
                    first: int = 0, normalize: bool = True,
                    scoring: str = "softmax", bias=None, scale: float = 1.0,
                    count=None, impl: str = "auto"):
    """One member's part of an expert layer divided over several: ``x``
    ``[N, d]`` and its router logits over ALL ``E`` experts ``[N, E]``,
    the matrices of the ``E_held`` experts held here (numbers ``first``
    onward) -> (``sum_{k chosen, held here} w_k expert_k(x)`` ``[N, d]``
    fp32, counts ``[4]`` int32: pairs routed, pairs held here, the
    fullest held expert's rows, held experts hit; the pairs of the
    rows ``count`` ``[N]`` bool names where given, so that a chunk's
    padding is not counted). The weights are normalised over all
    ``top_k`` chosen, held or not."""
    N, d = x.shape
    E, held = router_logits.shape[-1], w_gate.shape[0]
    idx, w = route(router_logits, top_k, normalize, scoring=scoring,
                   bias=bias, scale=scale)
    g, here = hold_share(idx, first, held, E)
    src = jnp.concatenate(
        [x.astype(w_gate.dtype), jnp.zeros((1, d), w_gate.dtype)]
    )
    rows = src[g.row_token]
    h = grouped_matmul_gate_up(rows, w_gate, w_up, g, impl=impl)
    y = grouped_matmul_down(h, w_down, g, impl=impl)
    # rows of dead tiles were never written: select, do not multiply
    kept = jnp.where(here[..., None], y[g.dest], 0.0)
    out = jnp.einsum("nkd,nk->nd", kept, w)
    if count is None:
        routed, held_here = jnp.int32(N * top_k), here.sum()
    else:
        routed, held_here = count.sum() * top_k, (here & count[:, None]).sum()
    stats = jnp.stack([
        routed, held_here, g.counts.max(), (g.counts > 0).sum(),
    ]).astype(jnp.int32)
    return out, stats


def moe_reference(x, router_logits, w_gate, w_up, w_down, *, top_k: int,
                  normalize: bool = True):
    """Every expert on every token, fp32: what the grouped path has to
    equal (tests and ``scripts/check_kernels.py``)."""
    idx, w = route(router_logits, top_k, normalize)
    x = x.astype(jnp.float32)
    f32 = lambda a: a.astype(jnp.float32)
    h = _silu(jnp.einsum("nd,edf->enf", x, f32(w_gate))) * jnp.einsum(
        "nd,edf->enf", x, f32(w_up)
    )
    y = jnp.einsum("enf,efd->end", h, f32(w_down))
    comb = jnp.zeros(router_logits.shape, jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], idx
    ].add(w)
    return jnp.einsum("ne,end->nd", comb, y)
