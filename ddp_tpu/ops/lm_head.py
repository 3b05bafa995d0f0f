"""The tied head and the next-token loss as ONE operation.

Where a train step wants the loss and not the logits, ``x @ embedᵀ``
and the cross-entropy over it are one function with its own VJP
(``head_loss``): the final hidden state, the embedding, the targets and
their weights in, the weighted sum of the per-token losses and the
weighted count of first-choice hits out. Written as three separate
steps (a matmul to float32 logits, ``optax``'s cross-entropy, an
arg-max) XLA hands the MXU float32 ``[rows, vocabulary]`` operands in a
layout a ragged vocabulary chose, passes over the logits twice for the
loss and makes the softmax's gradient twice more, once inside each
gradient matmul (PERF.md section 6, PR 42). Here:

- the operands of all three vocabulary matmuls are in the compute dtype
  (bfloat16 in the cells: the rounding the MXU performs on a float32
  operand at default precision, moved in front of the matmul: one head
  forward is bit-equal either way on the chip), the accumulation
  float32;
- the vocabulary is padded to the lanes' 128 INSIDE the operation (zero
  rows of the embedding, their logits masked to ``-inf`` before any
  statistic, their gradient rows never computed): the parameter keeps
  its shape, and the vocabulary is the minor dimension of every array;
- the float32 logits are written once, by the forward matmul, which
  takes the row maximum in its epilogue, and read once, by the one pass
  that takes every other statistic (the sum of exponentials, the
  target's logit, the first arg-max) and writes the logits' gradient:
  ``exp(logit - max)`` in the compute dtype, NEGATED at the row's
  target. That is the gradient up to what only the whole row knows (its
  sum) and what only the backward knows (the loss's cotangent): made
  ONCE, read by both gradient matmuls as it lies, each decoding it on
  its way into the MXU (``sign set: -(sum - e), else e``; the scale
  ``g · weight / sum`` goes into dX's epilogue and dW's small operand).
  No gather, no scatter, no mask and no second pass over a ``[rows,
  vocabulary]`` array.

It is XLA's own program, read from the compiled step before any chip
time and then measured (at the train cells' shape the three matmuls
run at 96%, 92% and 92% of the MXU's peak and the pass at 85% of the
HBM's, PERF.md section 5): an ``optimization_barrier`` on the stored
gradient is all that keeps XLA from re-deriving the exponentials inside
each matmul's fusion, as it does for the plain path, and a second one
speaks to its choice of schedule (``_head_loss_bwd``).

``lm.head_plan`` (``obs/tracer.SPAN_NUMS``) is recorded by the caller
that chooses between this and the plain path (``models/lm.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

LANES = 128


def padded_vocab(vocab: int) -> int:
    """The vocabulary as the operation's matmuls see it."""
    return -(-vocab // LANES) * LANES


def _padded(embed):
    return jnp.pad(
        embed, ((0, padded_vocab(embed.shape[0]) - embed.shape[0]), (0, 0)))


def _forward(hidden, embed, targets, weights):
    """((Σ weight · NLL, Σ weight · hit), what the backward reads)."""
    vocab, d = embed.shape
    rows = targets.size
    w = _padded(embed)
    x = hidden.reshape(rows, d).astype(w.dtype)
    t = targets.reshape(rows, 1)
    wts = jnp.broadcast_to(weights, targets.shape).reshape(rows)
    logits = lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    col = lax.broadcasted_iota(jnp.int32, (1, w.shape[0]), 1)
    if w.shape[0] != vocab:
        logits = jnp.where(col < vocab, logits, -jnp.inf)
    m = logits.max(-1, keepdims=True)
    # the first index at the maximum, as ``jnp.argmax`` has it; a
    # float32 minimum, so that it rides the pass of the sums (an integer
    # reduce gets a pass over the logits of its own)
    first = jnp.where(
        logits == m, col.astype(jnp.float32), float(w.shape[0])).min(-1)
    e = jnp.exp(logits - m)
    s = e.sum(-1)
    target_logit = jnp.where(col == t, logits, 0.0).sum(-1)
    grad = jnp.where(col == t, -e, e).astype(w.dtype)
    loss = (wts * (m[:, 0] + jnp.log(s) - target_logit)).sum()
    correct = (wts * (first == t[:, 0].astype(jnp.float32))).sum()
    return (loss, correct), (grad, s, x, embed, targets, wts, logits)


@jax.custom_vjp
def _head_loss(hidden, embed, targets, weights):
    return _forward(hidden, embed, targets, weights)[0]


def _head_loss_fwd(hidden, embed, targets, weights):
    out, (grad, s, *rest) = _forward(hidden, embed, targets, weights)
    # stored, not re-derived from the logits inside each gradient matmul
    grad, s = lax.optimization_barrier((grad, s))
    return out, (grad, s, *rest)


def _keep_until(value, buffer):
    """``value``, nominally not ready before ``buffer`` is: whoever
    reads the result keeps ``buffer`` alive until then, at no cost."""
    return lax.optimization_barrier((value, buffer))[0]


def _head_loss_bwd(saved, cts):
    grad, s, x, embed, targets, wts, logits = saved
    g, _ = cts  # the count has no gradient
    f32 = jnp.float32
    scale = (g * wts / s)[:, None]  # [rows, 1]

    def decoded(grad):
        # a set sign (of -0.0 too) marks the row's target: softmax - 1
        # there. Written into each matmul's own operand, not shared: a
        # shared one XLA stores as a third [rows, vocabulary] array
        e = grad.astype(f32)
        return jnp.where(
            jnp.signbit(e), -e - s[:, None], e).astype(grad.dtype)

    dx = lax.dot_general(
        decoded(grad), _padded(embed), (((1,), (0,)), ((), ())),
        preferred_element_type=f32) * scale
    # A word to XLA's choice of schedule, which takes the one of its
    # candidates with the lowest peak memory and, on a tie, the
    # depth-first one, which puts off every weight gradient (each fused
    # with its Adam update) to the end of the step, where the layers'
    # run 0.3 ms slower apiece, their activations no longer in VMEM
    # (measured, PERF.md section 6, PR 42: 3.1 ms a step at depth 8).
    # The plain path never tied: its float32 logits live until dW, so
    # putting dW off costs the depth-first candidate 1.65 GB for the
    # whole backward. Here they die with the statistics' pass, the peak
    # is that pass in every candidate, and the tie goes the wrong way.
    # So dW's small operand nominally waits for the logits: a schedule
    # that puts dW off pays for them again and loses, the greedy one
    # runs dW at once (it frees them and the stored gradient) and keeps
    # each layer's weight gradient beside its backward, as before.
    xs = _keep_until((x.astype(f32) * scale).astype(x.dtype), logits)
    # the padding's columns are sliced off the operand, not the result:
    # the matmul then writes [vocabulary, d] and no row is dropped after
    dw = lax.dot_general(
        decoded(grad[:, :embed.shape[0]]), xs, (((0,), (0,)), ((), ())),
        preferred_element_type=f32).astype(embed.dtype)
    return dx.reshape(targets.shape + x.shape[1:]), dw, None, None


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def head_loss(hidden, embed, targets, weights):
    """``hidden`` [..., d], ``embed`` [V, d] in the compute dtype,
    integer ``targets`` [...] and float32 ``weights`` broadcastable to
    them -> (Σ weight · NLL, Σ weight · [arg-max == target]), both
    float32 scalars. Differentiable in ``hidden`` and ``embed``."""
    return _head_loss(hidden.astype(jnp.float32), embed, targets, weights)
