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
  optionally renormalised (the published layer's ``norm_topk_prob``).
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

Forward only: serving never differentiates it.
"""

from __future__ import annotations

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


def route(logits, top_k: int, normalize: bool = True):
    """Router logits ``[N, E]`` -> (experts ``[N, top_k]`` int32,
    weights ``[N, top_k]`` fp32): softmax over ALL experts in fp32,
    the ``top_k`` largest (ties to the lower index), renormalised to
    sum to one when ``normalize``."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = lax.top_k(p, top_k)
    if normalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
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


def _gate_up_kernel(te_ref, live_ref, x_ref, wg_ref, wu_ref, h_ref):
    del te_ref

    @pl.when(pl.program_id(0) < live_ref[0])
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


def _grouped_call(kernel, name, rows, weights, g: Grouped, out_cols,
                  out_dtype, interpret):
    M, K = rows.shape
    tm = TILE_ROWS
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
