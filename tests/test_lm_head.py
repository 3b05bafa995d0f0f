"""The train step's tied head and next-token loss as ONE operation
(``ops/lm_head.head_loss``, chosen by ``models/lm.py``'s
``_make_sharded_token_metrics``) against the plain path it replaces:
``hidden @ embedᵀ`` -> ``next_token_loss`` + ``jnp.argmax``, on the same
inputs. Loss, count of first-choice hits, gradient of the hidden state
and of the embedding; a vocabulary the lanes' 128 does not divide;
ties; the padding's rows; every mesh the loss shard runs in; which form
the code chooses, and the record that says so.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddp_tpu.models.lm import (
    LMSpec,
    _make_sharded_token_metrics,
    create_lm_train_state,
    dense_lm_apply,
    make_lm_eval_step,
    make_lm_train_step,
    next_token_loss,
)
from ddp_tpu.ops.lm_head import head_loss, padded_vocab
from ddp_tpu.runtime.mesh import MeshSpec, make_mesh

D = 32


def _inputs(vocab, batch=4, length=16, seed=0):
    k = jax.random.split(jax.random.key(seed), 3)
    hidden = jax.random.normal(k[0], (batch, length, D), jnp.float32)
    embed = 0.3 * jax.random.normal(k[1], (vocab, D), jnp.float32)
    tokens = jax.random.randint(k[2], (batch, length), 0, vocab, jnp.int32)
    return hidden, embed, tokens


def _plain(hidden, embed, tokens):
    """The path the operation replaces: (mean loss, correct count)."""
    logits = hidden @ embed.T
    pred = jnp.argmax(logits[:, :-1], -1)
    return (next_token_loss(logits, tokens),
            (pred == tokens[:, 1:]).sum().astype(jnp.float32))


def _records(since):
    """``lm.head_plan`` records stamped after ``since``: by the record's
    own start, not by its place in a ring that turns over."""
    from ddp_tpu.obs.tracer import get_tracer

    return [e for e in get_tracer().ring()
            if e[0] == "lm.head_plan" and e[1] >= since]


@pytest.mark.parametrize("case", [
    "vocab_one_below_a_multiple", "vocab_one_above_a_multiple",
    "vocab_a_multiple", "mesh_data4", "mesh_data2_seq2",
    "mesh_data2_model2", "ties_go_to_the_first_index",
    "padding_gets_no_probability_and_no_gradient", "bfloat16_operands",
])
def test_fused_head_matches_the_plain_path(devices, case):
    vocab = {"vocab_one_below_a_multiple": 127,
             "vocab_one_above_a_multiple": 129,
             "vocab_a_multiple": 256}.get(case, 200)
    axes = {"mesh_data4": dict(data=4), "mesh_data2_seq2": dict(data=2, seq=2),
            "mesh_data2_model2": dict(data=2, model=2)}.get(
                case, dict(data=1))
    n = int(np.prod(list(axes.values())))
    mesh = make_mesh(MeshSpec(**axes), devices=devices[:n])
    hidden, embed, tokens = _inputs(vocab)
    if case == "ties_go_to_the_first_index":
        # rows 3, 17 and 150 of the embedding equal: their logits tie
        # everywhere; where they are a row's maximum the first wins
        embed = embed.at[17].set(embed[3]).at[150].set(embed[3])
        hidden = hidden.at[:, ::2].set(4.0 * embed[3])
        tokens = tokens.at[:, 1::4].set(3).at[:, 3::4].set(17)
    spec = LMSpec(vocab_size=vocab, total_len=tokens.shape[1], d_model=D)
    metrics = _make_sharded_token_metrics(spec, mesh)
    assert metrics.fused

    def fused(hidden, embed):
        # every device's own copy, as the forward's shard hands it over
        return metrics((hidden, jnp.broadcast_to(embed, (n, *embed.shape))),
                       tokens)

    if case == "bfloat16_operands":
        # the cells' compute dtype: the embedding arrives in bfloat16
        # and the hidden state is rounded to it once; the plain path
        # over the same ROUNDED operands is float32 arithmetic here
        embed = embed.astype(jnp.bfloat16)
        rounded = hidden.astype(jnp.bfloat16).astype(jnp.float32)
        want, want_grads = jax.value_and_grad(
            lambda h, e: _plain(h, e.astype(jnp.float32), tokens),
            argnums=(0, 1), has_aux=True)(rounded, embed)
        tol = dict(rtol=2e-2, atol=2e-3)  # exp() and dlogits in bfloat16
    else:
        want, want_grads = jax.value_and_grad(
            lambda h, e: _plain(h, e, tokens), argnums=(0, 1),
            has_aux=True)(hidden, embed)
        tol = dict(rtol=2e-5, atol=2e-6)
    since = time.perf_counter()
    got, got_grads = jax.jit(jax.value_and_grad(
        fused, argnums=(0, 1), has_aux=True))(hidden, embed)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-3 if case ==
                               "bfloat16_operands" else 1e-6)
    assert float(got[1]) == float(want[1])
    if case == "ties_go_to_the_first_index":
        assert float(got[1]) >= 16  # the tied rows that name row 3
    for g, w in zip(got_grads, want_grads):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32), **tol)
    # one record a traced call of a shard's loss, and it names the form
    # (``model`` members hold the same rows)
    rows = tokens.size // (axes["data"] * axes.get("seq", 1))
    assert [r[4] for r in _records(since)] == [
        ("fused", rows, vocab, padded_vocab(vocab), rows)]
    if case == "padding_gets_no_probability_and_no_gradient":
        # the same rows through the operation with the padding made
        # REAL rows of a 256-row embedding, at -inf by hand: the padded
        # call must be that call, its padding contributing nothing
        assert padded_vocab(vocab) == 256
        targets, weights = tokens, jnp.ones(tokens.shape, jnp.float32)
        loss, grads = jax.value_and_grad(
            lambda h, e: head_loss(h, e, targets, weights)[0],
            argnums=(0, 1))(hidden, embed)
        big = jnp.concatenate(
            [embed, jnp.ones((256 - vocab, D), jnp.float32)])

        def by_hand(h, e):
            logits = jnp.where(jnp.arange(256) < vocab, h @ e.T, -jnp.inf)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, targets).sum()

        loss_big, grads_big = jax.value_and_grad(
            by_hand, argnums=(0, 1))(hidden, big)
        np.testing.assert_allclose(loss, loss_big, rtol=1e-6)
        np.testing.assert_allclose(grads[0], grads_big[0], atol=2e-6)
        np.testing.assert_allclose(grads[1], grads_big[1][:vocab], atol=2e-6)
        assert not np.asarray(grads_big[1][vocab:]).any()
        assert grads[1].shape == embed.shape


@pytest.mark.parametrize("what", [
    "label_smoothing_takes_the_plain_path",
    "the_eval_step_takes_the_plain_path",
    "the_train_step_takes_the_fused_path",
])
def test_the_form_follows_what_the_loss_needs(devices, what):
    """No knob: integer targets without label smoothing run head and
    loss as one operation; label smoothing (every log-probability's
    sum) and a caller that wants logits keep the head they had. Each
    traced call of the loss leaves ONE ``lm.head_plan`` record with the
    form that ran, and a call of the compiled step leaves none."""
    spec = LMSpec(vocab_size=200, total_len=16, d_model=D, depth=1,
                  num_heads=2)
    mesh = make_mesh(MeshSpec(data=2), devices=devices[:2])
    tx = optax.adam(1e-3)
    state = create_lm_train_state(spec, tx, mesh, seed=0)
    _, _, tokens = _inputs(200)
    logits = dense_lm_apply(spec, state.params, tokens)
    since = time.perf_counter()
    if what == "the_eval_step_takes_the_plain_path":
        step = make_lm_eval_step(spec, mesh)
        acc, loss = step(state.params, None, tokens, None,
                         jnp.ones((4,), jnp.float32))
        np.testing.assert_allclose(
            float(loss) / 4, float(next_token_loss(logits, tokens)),
            rtol=1e-5)
        assert _records(since) == []  # it has logits: no plan to record
        return
    smoothing = 0.1 if what.startswith("label_smoothing") else 0.0
    step = make_lm_train_step(
        spec, tx, mesh, donate=False, label_smoothing=smoothing)
    _, m = step(state, tokens)
    np.testing.assert_allclose(
        float(m.loss),
        float(next_token_loss(logits, tokens, label_smoothing=smoothing)),
        rtol=1e-5)
    want_acc = float(
        (jnp.argmax(logits[:, :-1], -1) == tokens[:, 1:]).mean())
    np.testing.assert_allclose(float(m.accuracy), want_acc, rtol=1e-6)
    rows = tokens.size // 2
    form = ("plain", rows, 200, 200, rows) if smoothing else (
        "fused", rows, 200, 256, rows)
    assert [r[4] for r in _records(since)] == [form]
    step(state, tokens)  # compiled: nothing traced, nothing recorded
    assert len(_records(since)) == 1
