"""Selective state-space (Mamba-2 and Mamba-1) operators for serving.

A Mamba-2 head ``h`` keeps a state ``S`` in ``R^{P x N}`` (P channels of
the head, N state dimensions) and advances it one token at a time:

    S_t = exp(dt_t A) S_{t-1} + dt_t * xs_t B_t^T        y_t = S_t C_t + D xs_t

with ``dt_t > 0`` and ``A < 0`` one a head, ``xs_t`` the head's P inputs,
``B_t`` and ``C_t`` the N-vectors all heads share (one group). This
module holds the three operators the recurrence needs and nothing of a
model:

- :func:`ssm_state_update`, the decode step: every LIVE lane of one
  layer advances one token. On the chip it is one ``pallas_call`` named
  ``ssm_state_update`` that reads a live lane's state once, writes it
  IN PLACE into the donated buffer once and forms ``y`` on the way, so
  the step moves each live state byte once in and once out; an idle
  lane's state is neither read nor written. Plain XLA passes over the
  state three times (update, write-back, contraction with ``C``).
- :func:`ssd_scan`, prefill: the same recurrence over a run of tokens
  from a carried state to a final state, in the chunked ("SSD") form,
  four einsums and a cumulative sum a chunk, under the scope
  ``ssd_scan``. A position whose ``dt`` is 0 leaves the state as it is
  and adds nothing: that is how a padded chunk stays inert.
- :func:`causal_conv` / :func:`conv_step` / :func:`conv_tail`, the
  depthwise causal convolution in front of the recurrence, over a run
  of tokens and one token at a time from a rolling tail of the last
  ``K - 1`` inputs.

**State layout.** A layer's state is stored ``[N, H*P]``: state
dimension on sublanes, the layer's ``H*P`` channels on lanes. Then the
decay ``exp(dt A)`` and the input ``dt xs`` are row vectors over
channels, ``B`` and ``C`` are column vectors over ``N``, the update is
two broadcast multiplies and an add on whole vregs, and ``y`` is a sum
over sublanes; the ``[H, P, N]`` layout of the equations needs a lane
broadcast of every per-channel scalar and a lane reduction for every
``y``. Same bytes either way (``N * H * P`` float32 a lane a layer).

**Mamba-1** (the second half of the module) decays every (state index,
channel) pair by its own ``exp(dt_c A_nc)``: ``dt`` one a channel, ``A``
``[N, C]``, no heads. :func:`selective_state_update` is the decode step
through :func:`ssm_state_update`'s kernel with the decay formed inside
it; :func:`selective_scan` is prefill, a Pallas kernel that keeps the
state of a tile of channels in VMEM and walks the time steps (there is
no matmul form). Same layout, same rules for idle lanes and padding.

The state is float32 throughout. ``ssd_scan``'s einsums take their
operands in ``dtype`` (the model passes its weights' dtype: bfloat16 on
the chip, float32 at ``highest`` in tests) and accumulate in float32;
decays, cumulative sums and the state itself stay float32.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddp_tpu.obs.tracer import get_tracer

# Channels (lanes) of one lane's state a grid step holds: [N 128, 2048]
# float32 is 1 MB in and 1 MB out, double-buffered 4 MB of a v5e's
# 16 MB of scoped VMEM, beside 2 MB of temporaries.
DEFAULT_CHANNELS_PER_TILE = 2048
_TILE_STATE_ROWS = 128  # the N the default was sized at


def resolve_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl not in ("pallas", "jnp"):
        raise ValueError(
            f"unknown ssm impl {impl!r}: expected 'auto', 'pallas' or 'jnp'"
        )
    return impl


# ---- the convolution ---------------------------------------------------


def causal_conv(x, tail, w, b):
    """Depthwise causal convolution over a run of tokens.

    ``x`` ``[T, C]`` the run, ``tail`` ``[K-1, C]`` the inputs just
    before it (zeros at a sequence's start), ``w`` ``[K, C]`` with
    ``w[K-1]`` on the current token, ``b`` ``[C]`` ->
    ``y[t] = b + sum_k w[k] * x[t - (K-1) + k]`` ``[T, C]``, float32."""
    K, T = w.shape[0], x.shape[0]
    full = jnp.concatenate([tail, x], axis=0).astype(jnp.float32)
    w = w.astype(jnp.float32)
    y = b.astype(jnp.float32)[None, :]
    for k in range(K):
        y = y + w[k][None, :] * lax.slice_in_dim(full, k, k + T, axis=0)
    return y


def conv_tail(x, tail, length):
    """The tail a run leaves behind: the last ``K-1`` inputs up to and
    including position ``length - 1`` of ``x`` (``length`` traced; the
    positions after it are padding), reaching back into ``tail`` where
    the run is shorter than ``K-1``."""
    full = jnp.concatenate([tail, x], axis=0).astype(jnp.float32)
    return lax.dynamic_slice_in_dim(full, length, tail.shape[0], axis=0)


def conv_step(x, tail, w, b):
    """One token a lane: ``x`` ``[S, C]``, ``tail`` ``[S, K-1, C]`` ->
    (``y`` ``[S, C]`` float32, the tail rolled one token on)."""
    full = jnp.concatenate(
        [tail.astype(jnp.float32), x.astype(jnp.float32)[:, None]], axis=1
    )
    y = b.astype(jnp.float32)[None, :] + jnp.sum(
        full * w.astype(jnp.float32)[None], axis=1
    )
    return y, full[:, 1:]


# ---- prefill: the chunked scan -----------------------------------------


def _scan_block(x, dt, A, B, C, state, dtype):
    """One chunk of ``Q`` tokens from ``state`` ``[N, H, P]``."""
    Q = x.shape[0]
    prec = lax.Precision.HIGHEST if dtype == jnp.float32 else None
    ein = functools.partial(
        jnp.einsum, preferred_element_type=jnp.float32, precision=prec
    )
    c = lambda a: a.astype(dtype)
    cum = jnp.cumsum(dt * A[None, :], axis=0)  # [Q, H], <= 0, falling
    t = jnp.arange(Q)
    # decay[h, t, s] = exp(sum_{s < r <= t} dt_r A): what is left at t
    # of what entered at s. Masked BEFORE the exp: above the diagonal
    # the difference is positive and may overflow.
    diff = cum.T[:, :, None] - cum.T[:, None, :]
    decay = jnp.exp(jnp.where(t[:, None] >= t[None, :], diff, -jnp.inf))
    xdt = x * dt[:, :, None]  # [Q, H, P]
    scores = ein("tn,sn->ts", c(C), c(B))
    y = ein("hts,shp->thp", c(decay * scores[None]), c(xdt))
    y = y + ein("tn,nhp->thp", c(C), c(state)) * jnp.exp(cum)[:, :, None]
    to_end = jnp.exp(cum[-1][None, :] - cum)  # [Q, H]
    # channels flat, as the state is stored (and the CPU backend has
    # no bfloat16 dot for the three-dimensional form)
    state = state * jnp.exp(cum[-1])[None, :, None] + ein(
        "sn,sq->nq", c(B), c(xdt * to_end[:, :, None]).reshape(Q, -1)
    ).reshape(state.shape)
    return y, state


@jax.named_scope("ssd_scan")
def ssd_scan(x, dt, A, B, C, state, *, chunk: int = 256,
             dtype=jnp.float32):
    """The recurrence over ``T`` tokens of one lane, chunked.

    ``x`` ``[T, H, P]``, ``dt`` ``[T, H]`` (after softplus; 0 at a
    position that must not move the state), ``A`` ``[H]`` (negative),
    ``B``, ``C`` ``[T, N]``, ``state`` ``[N, H*P]`` float32 the state
    before the first token -> (``y`` ``[T, H, P]`` WITHOUT the ``D``
    term, the state after the last token ``[N, H*P]``). ``T`` need not
    be a multiple of ``chunk``: the run is padded with ``dt = 0``."""
    T, H, P = x.shape
    N = B.shape[-1]
    f32 = lambda a: a.astype(jnp.float32)
    x, dt, A, B, C = f32(x), f32(dt), f32(A), f32(B), f32(C)
    s0 = f32(state).reshape(N, H, P)
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        zero = lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        x, dt, B, C = zero(x), zero(dt), zero(B), zero(C)
    n = (T + pad) // Q
    if n == 1:
        y, s = _scan_block(x, dt, A, B, C, s0, dtype)
    else:
        blocks = tuple(a.reshape(n, Q, *a.shape[1:]) for a in (x, dt, B, C))

        def step(s, blk):
            y, s = _scan_block(*blk[:2], A, *blk[2:], s, dtype)
            return s, y

        s, y = lax.scan(step, s0, blocks)
        y = y.reshape(n * Q, H, P)
    return y[:T], s.reshape(N, H * P)


# ---- decode: one token a live lane --------------------------------------


def live_lanes(live):
    """``live`` ``[S]`` bool -> (the lanes' indices with the live ones
    first, in order; how many are live): what the kernel's index maps
    read. Computed once a decode step, shared by every layer."""
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return order, jnp.sum(live).astype(jnp.int32)[None]


def _terms(x, dt, A, D):
    """Per-channel row vectors of the update: decay, input, skip."""
    S, H, P = x.shape
    wide = lambda a: jnp.broadcast_to(a[:, :, None], (S, H, P)).reshape(
        S, H * P)
    da = wide(jnp.exp(dt * A[None, :]))
    dtx = (x * dt[:, :, None]).reshape(S, H * P)
    skip = x * D[None, :, None]
    return da, dtx, skip


def state_update_reference(state, layer: int, x, dt, A, B, C, D, live):
    """The update in plain ``jax.numpy``: every lane's state of
    ``layer`` is read, a live lane's is advanced, and the layer is
    written back. What the kernel is pinned against."""
    f32 = lambda a: a.astype(jnp.float32)
    x, dt, A, B, C, D = f32(x), f32(dt), f32(A), f32(B), f32(C), f32(D)
    da, dtx, skip = _terms(x, dt, A, D)
    old = state[layer]  # [S, N, HP]
    new = old * da[:, None, :] + B[:, :, None] * dtx[:, None, :]
    y = jnp.sum(new * C[:, :, None], axis=1).reshape(x.shape) + skip
    keep = live[:, None, None]
    state = state.at[layer].set(jnp.where(keep, new, old))
    return state, jnp.where(keep, y, 0.0)


def _update_kernel(order_ref, n_ref, s_ref, da_ref, dtx_ref, b_ref, c_ref,
                   *rest, per_element: bool):
    """``per_element``: ``da_ref`` holds ``dt`` ``[1, cb]`` and the
    operand after ``c_ref`` is ``A`` ``[N, cb]``; the decay of every
    (state index, channel) pair is formed here (Mamba-1). Otherwise
    ``da_ref`` is the channels' decay as it stands (Mamba-2: one a
    head, widened outside)."""
    *a_ref, o_ref, y_ref = rest
    j, c = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]

    @pl.when(j < n)
    def _live():
        decay = jnp.exp(da_ref[...] * a_ref[0][...]) if per_element \
            else da_ref[...]
        new = s_ref[...] * decay + b_ref[...] * dtx_ref[...]
        o_ref[...] = new
        y_ref[...] = jnp.sum(new * c_ref[...], axis=0, keepdims=True)

    # No lane is live: every grid step names one block, which is
    # fetched and written back once, so it has to go back as it came.
    @pl.when((n == 0) & (j == 0) & (c == 0))
    def _none():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def channels_per_tile(channels: int, want: int | None = None) -> int:
    """The widest tile-aligned divisor of ``channels`` at or under
    ``want`` (the whole layer where it is narrower than a tile)."""
    want = want or DEFAULT_CHANNELS_PER_TILE
    if channels <= want:
        return channels
    for cb in range(want - want % 128, 0, -128):
        if channels % cb == 0:
            return cb
    raise ValueError(
        f"ssm_state_update: {channels} channels have no divisor that is a "
        f"multiple of 128 at or under {want}"
    )


def _update_call(state, layer: int, da, dtx, B, C, live, *, name: str,
                 A=None, lanes=None, tile: int | None = None,
                 interpret: bool | None = None):
    """The kernel. Grid ``(S, channels / tile)``: step ``(j, c)`` holds
    channels ``c`` of the ``j``-th LIVE lane's state, ``[N, tile]``;
    the steps after the last live lane repeat its last block, which
    Pallas neither fetches nor writes again, and compute nothing.
    ``da``, ``dtx`` ``[S, channels]`` row vectors, ``B``, ``C``
    ``[S, N]``; with ``A`` ``[N, channels]`` (the same for every lane,
    fetched a tile at a time) ``da`` is ``dt`` and the decay is formed
    in the kernel. Returns (state, ``y`` ``[S, channels]``)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S = da.shape[0]
    N, HP = state.shape[2], state.shape[3]
    cb = channels_per_tile(HP, tile)
    nc = HP // cb
    order, n_live = lanes if lanes is not None else live_lanes(live)

    def lane(j, order_ref, n_ref):
        return order_ref[jnp.clip(jnp.minimum(j, n_ref[0] - 1), 0, S - 1)]

    def chan(j, c, n_ref):
        return jnp.where(j < n_ref[0], c, nc - 1)

    def state_map(j, c, order_ref, n_ref):
        return (layer, lane(j, order_ref, n_ref), 0, chan(j, c, n_ref))

    def row_map(j, c, order_ref, n_ref):
        return (lane(j, order_ref, n_ref), 0, chan(j, c, n_ref))

    def col_map(j, c, order_ref, n_ref):
        return (lane(j, order_ref, n_ref), 0, 0)

    vmem = {"memory_space": pltpu.VMEM}
    state_spec = pl.BlockSpec((None, None, N, cb), state_map, **vmem)
    row_spec = pl.BlockSpec((None, 1, cb), row_map, **vmem)
    col_spec = pl.BlockSpec((None, N, 1), col_map, **vmem)
    in_specs = [state_spec, row_spec, row_spec, col_spec, col_spec]
    args = [state, da[:, None, :], dtx[:, None, :], B[:, :, None],
            C[:, :, None]]
    if A is not None:
        in_specs.append(pl.BlockSpec(
            (N, cb), lambda j, c, order_ref, n_ref: (0, chan(j, c, n_ref)),
            **vmem))
        args.append(A)
    state, y = pl.pallas_call(
        functools.partial(_update_kernel, per_element=A is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, nc),
            in_specs=in_specs,
            out_specs=[state_spec, row_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((S, 1, HP), jnp.float32),
        ],
        # operand 2 (after the two prefetched scalars) is the state
        input_output_aliases={2: 0},
        interpret=interpret,
        name=name,
    )(order, n_live, *args)
    return state, y.reshape(S, HP)


def state_update_pallas(state, layer: int, x, dt, A, B, C, D, live, *,
                        lanes=None, tile: int | None = None,
                        interpret: bool | None = None):
    """Mamba-2's update through :func:`_update_call`: one decay a
    head, widened to its channels outside the kernel."""
    f32 = lambda a: a.astype(jnp.float32)
    x, dt, A, B, C, D = f32(x), f32(dt), f32(A), f32(B), f32(C), f32(D)
    S, H, P = x.shape
    da, dtx, skip = _terms(x, dt, A, D)
    state, y = _update_call(
        state, layer, da, dtx, B, C, live, name="ssm_state_update",
        lanes=lanes, tile=tile, interpret=interpret)
    # Trace time, as ``flash.plan``: a compiled step leaves none.
    cb = channels_per_tile(H * P, tile)
    get_tracer().complete(
        "ssm.plan", time.perf_counter(), 0.0,
        nums=("ssm_state_update", 1, max(1, cb // P), str(state.dtype)),
    )
    y = y.reshape(S, H, P) + skip
    return state, jnp.where(live[:, None, None], y, 0.0)


def ssm_state_update(state, layer: int, x, dt, A, B, C, D, live, *,
                     impl: str = "auto", lanes=None,
                     tile: int | None = None,
                     interpret: bool | None = None):
    """Advance the live lanes of ``layer`` one token.

    ``state`` ``[layers, S, N, H*P]`` float32, the whole stored buffer
    (donated by the caller's program; ``layer`` is Python-static, so no
    layer is sliced out); ``x`` ``[S, H, P]``, ``dt`` ``[S, H]`` after
    softplus, ``A`` and ``D`` ``[H]``, ``B`` and ``C`` ``[S, N]``,
    ``live`` ``[S]`` bool; ``lanes`` what :func:`live_lanes` gives for
    ``live``, where the caller has it already. Returns (the buffer with
    the live lanes' states of ``layer`` advanced and every other byte
    as it was, ``y`` ``[S, H, P]`` float32 with the ``D`` term, zero on
    an idle lane)."""
    if resolve_impl(impl) == "pallas":
        return state_update_pallas(
            state, layer, x, dt, A, B, C, D, live, lanes=lanes, tile=tile,
            interpret=interpret,
        )
    return state_update_reference(state, layer, x, dt, A, B, C, D, live)


# ---- Mamba-1: a decay for every (state index, channel) pair -------------
#
# ``h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] B_t[n] x_t[c]``,
# ``y_t[c] = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]``: ``dt`` one a channel,
# ``A`` ``[N, C]`` stored as the state is laid out (state index on
# sublanes, channels on lanes). There are no heads, and the decay cannot
# be widened from a per-head scalar outside the kernel without writing an
# array the size of the state: it is formed inside, from ``dt`` and a
# tile of ``A``.


def selective_update_reference(state, layer: int, x, dt, A, B, C, D, live):
    """Plain ``jax.numpy``: what the kernel is pinned against."""
    f32 = lambda a: a.astype(jnp.float32)
    x, dt, A, B, C, D = f32(x), f32(dt), f32(A), f32(B), f32(C), f32(D)
    old = state[layer]  # [S, N, C]
    new = (old * jnp.exp(dt[:, None, :] * A[None])
           + B[:, :, None] * (dt * x)[:, None, :])
    y = jnp.sum(new * C[:, :, None], axis=1) + x * D[None, :]
    keep = live[:, None, None]
    state = state.at[layer].set(jnp.where(keep, new, old))
    return state, jnp.where(live[:, None], y, 0.0)


def selective_state_update(state, layer: int, x, dt, A, B, C, D, live, *,
                           impl: str = "auto", lanes=None,
                           tile: int | None = None,
                           interpret: bool | None = None):
    """Advance the live lanes of ``layer`` one token, Mamba-1.

    ``state`` ``[layers, S, N, C]`` float32, the whole stored buffer
    (donated; ``layer`` static); ``x``, ``dt`` ``[S, C]`` (``dt`` after
    softplus), ``A`` ``[N, C]`` (negative), ``B``, ``C`` ``[S, N]``,
    ``D`` ``[C]``, ``live`` ``[S]`` bool. Returns (the buffer with the
    live lanes' states of ``layer`` advanced and every other byte as it
    was, ``y`` ``[S, C]`` float32 with the ``D`` term, zero on an idle
    lane). On the chip :func:`ssm_state_update`'s kernel under the name
    ``selective_state_update``: same grid over live lanes and channel
    tiles, same in-place write."""
    if resolve_impl(impl) != "pallas":
        return selective_update_reference(state, layer, x, dt, A, B, C, D,
                                          live)
    f32 = lambda a: a.astype(jnp.float32)
    x, dt, A, B, C, D = f32(x), f32(dt), f32(A), f32(B), f32(C), f32(D)
    # The same BYTES a grid step as the default holds at N 128: with 16
    # state indices a lane's whole [16, 5120] layer (328 KB) is one step,
    # where tiles of 1280 took 4 steps of 82 KB and the steps' own cost
    # (~0.4 us) was two thirds of a call (PERF.md section 6, PR 36).
    tile = tile or (DEFAULT_CHANNELS_PER_TILE * _TILE_STATE_ROWS
                    // state.shape[2])
    state, y = _update_call(
        state, layer, dt, dt * x, B, C, live, A=A,
        name="selective_state_update", lanes=lanes, tile=tile,
        interpret=interpret)
    get_tracer().complete(
        "ssm.plan", time.perf_counter(), 0.0,
        nums=("selective_state_update", 1,
              channels_per_tile(x.shape[1], tile), str(state.dtype)),
    )
    return state, jnp.where(live[:, None], y + x * D[None, :], 0.0)


def selective_scan_reference(x, dt, A, B, C, state):
    """The recurrence one token after another (``lax.scan``)."""

    def step(h, t):
        x_t, dt_t, B_t, C_t = t
        h = h * jnp.exp(dt_t[None, :] * A) + B_t[:, None] * (dt_t * x_t)[None]
        return h, jnp.sum(h * C_t[:, None], axis=0)

    state, y = lax.scan(step, state, (x, dt, B, C))
    return y, state


# Time steps a grid step of the scan holds, and the steps unrolled
# between two aligned stores of ``y``: [8, tile] float32 is one row of
# vregs.
SCAN_TIME_BLOCK = 128
_SCAN_UNROLL = 8


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, s_ref, y_ref, o_ref,
                 h_ref):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _start():
        h_ref[...] = s_ref[...]

    A = a_ref[...]

    def group(g, h):
        rows = pl.ds(pl.multiple_of(g * _SCAN_UNROLL, _SCAN_UNROLL),
                     _SCAN_UNROLL)
        dt, dtx = dt_ref[rows, :], dt_ref[rows, :] * x_ref[rows, :]
        ys = []
        for i in range(_SCAN_UNROLL):
            r = g * _SCAN_UNROLL + i
            h = h * jnp.exp(dt[i:i + 1] * A) + b_ref[r] * dtx[i:i + 1]
            ys.append(jnp.sum(h * c_ref[r], axis=0, keepdims=True))
        y_ref[rows, :] = jnp.concatenate(ys, axis=0)
        return h

    h_ref[...] = lax.fori_loop(0, x_ref.shape[0] // _SCAN_UNROLL, group,
                               h_ref[...])

    @pl.when(t == pl.num_programs(1) - 1)
    def _end():
        o_ref[...] = h_ref[...]


def selective_scan_pallas(x, dt, A, B, C, state, *, tile: int | None = None,
                          interpret: bool | None = None):
    """Grid ``(channels / tile, T / SCAN_TIME_BLOCK)``, time innermost:
    the state of a tile of channels stays in VMEM while the kernel
    walks the time steps; ``x``, ``dt``, ``B``, ``C`` are read once and
    ``y`` written once."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T, Cn = x.shape
    N = A.shape[0]
    tb = min(SCAN_TIME_BLOCK, T + -T % _SCAN_UNROLL)
    pad = -T % tb
    if pad:
        zero = lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        x, dt, B, C = zero(x), zero(dt), zero(B), zero(C)
    cb = channels_per_tile(Cn, tile or 1024)
    get_tracer().complete(
        "ssm.plan", time.perf_counter(), 0.0,
        nums=("selective_scan", 1, cb, str(state.dtype)),
    )
    vmem = {"memory_space": pltpu.VMEM}
    row = pl.BlockSpec((tb, cb), lambda c, t: (t, c), **vmem)
    col = pl.BlockSpec((tb, N, 1), lambda c, t: (t, 0, 0), **vmem)
    tile_spec = pl.BlockSpec((N, cb), lambda c, t: (0, c), **vmem)
    y, state = pl.pallas_call(
        _scan_kernel,
        grid=(Cn // cb, (T + pad) // tb),
        in_specs=[row, row, col, col, tile_spec, tile_spec],
        out_specs=[row, tile_spec],
        out_shape=[
            jax.ShapeDtypeStruct((T + pad, Cn), jnp.float32),
            jax.ShapeDtypeStruct((N, Cn), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, cb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(x, dt, B[:, :, None], C[:, :, None], A, state)
    return y[:T], state


@jax.named_scope("selective_scan")
def selective_scan(x, dt, A, B, C, state, *, impl: str = "auto",
                   tile: int | None = None, interpret: bool | None = None):
    """The Mamba-1 recurrence over ``T`` tokens of one lane.

    ``x``, ``dt`` ``[T, C]`` (``dt`` after softplus; 0 at a position
    that must not move the state), ``A`` ``[N, C]`` (negative), ``B``,
    ``C`` ``[T, N]``, ``state`` ``[N, C]`` float32 the state before the
    first token -> (``y`` ``[T, C]`` WITHOUT the ``D`` term, the state
    after the last token). There is no matmul form: the decay differs
    by state index. ``"pallas"`` is :func:`selective_scan_pallas`;
    ``"jnp"`` a ``lax.scan`` of ``T`` dependent steps."""
    f32 = lambda a: a.astype(jnp.float32)
    x, dt, A, B, C, state = f32(x), f32(dt), f32(A), f32(B), f32(C), f32(state)
    if resolve_impl(impl) == "pallas":
        return selective_scan_pallas(x, dt, A, B, C, state, tile=tile,
                                     interpret=interpret)
    return selective_scan_reference(x, dt, A, B, C, state)
