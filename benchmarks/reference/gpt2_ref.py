"""The plain reference: a GPT-2 block stack in float32 ``jax.numpy``.

Forward, next-token loss and gradient, written straight from the
published description (Radford et al. 2019; the Cerebras-GPT config is
this block with learned positions, pre-LayerNorm, a GELU MLP of ratio 4
and a tied head). No kernels, no cache, no batching tricks. Every
matmul runs under ``jax.default_matmul_precision("highest")`` — on a
TPU a float32 matmul is otherwise done in bfloat16 passes.

Imports nothing of the program under test. Departures from the
published model, both the program's own: the fused qkv kernel is laid
out head-major ``[head, (q|k|v), head_dim]``, and GELU is the tanh
approximation (``gelu_new`` in the published config).

``precision`` selects the CONTROL: the same mathematics computed in the
next precision below the one a configuration states, which the
comparison that decides ``correct`` has to reject.

- ``"float32"``: the reference itself.
- ``"bfloat16"``: weights and activations rounded to bfloat16 before
  every matmul and after every layer (control for a float32 program).
- ``"float8"``: every matmul operand rounded to float8 with a
  per-tensor scale, e4m3 forward and e5m2 for the gradient flowing back
  (control for a bfloat16 program).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-6  # Flax LayerNorm's default, which the program uses


def _fp8(x, dtype, fmax: float):
    """Round to an 8-bit float with a per-tensor scale, as fp8 recipes
    do (the largest magnitude maps to the format's largest)."""
    scale = fmax / (jnp.max(jnp.abs(x)) + 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8_operand(x):
    """A matmul operand in float8: e4m3 forward, and the gradient that
    flows back through it in e5m2 (the usual split: range for gradients,
    precision for activations), each with its own per-tensor scale."""
    return _fp8(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return _fp8_operand(x), None


def _fp8_bwd(_, ct):
    return (_fp8(ct, jnp.float8_e5m2, 57344.0),)


_fp8_operand.defvjp(_fp8_fwd, _fp8_bwd)


def _round_operand(x, precision: str):
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        return _fp8_operand(x)
    raise ValueError(f"unknown precision {precision!r}")


def _mm(a, w, precision: str, ready: bool = False):
    """activation @ weight. ``ready``: the weight was rounded already
    (``round_weights``), once a step instead of once a use."""
    if not ready:
        w = _round_operand(w, precision)
    return jnp.matmul(_round_operand(a, precision), w, precision="highest")


def round_weights(params, precision: str):
    """Every matmul weight rounded to the control's precision, plus the
    tied head's copy of the embedding under ``head`` (the lookup keeps
    the unrounded table). Gradients flow back through the rounding."""
    def walk(tree):
        return {
            k: walk(v) if isinstance(v, dict)
            else (_round_operand(v, precision) if k == "kernel" else v)
            for k, v in tree.items()
        }

    out = walk(params)
    out["head"] = _round_operand(params["embed"], precision)
    return out


def _act(x, precision: str):
    """Where a lower-precision program would keep its activations."""
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def layer_norm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def attention(x, p, num_heads: int, precision: str, ready: bool = False):
    """Causal multi-head self-attention over [B, T, d]."""
    B, T, d = x.shape
    hd = d // num_heads
    qkv = _mm(x, p["qkv"]["kernel"], precision, ready) + p["qkv"]["bias"]
    qkv = qkv.reshape(B, T, num_heads, 3, hd)
    q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
    q, k, v = (_round_operand(t, precision) for t in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest")
    s = s / jnp.sqrt(jnp.float32(hd))
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    w = _round_operand(w, precision)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision="highest")
    o = _act(o.reshape(B, T, d), precision)
    return _mm(o, p["proj"]["kernel"], precision, ready) + p["proj"]["bias"]


def block(x, p, num_heads: int, precision: str = "float32",
          ready: bool = False):
    x = x + _act(
        attention(layer_norm(x, p["ln1"]), p["attn"], num_heads, precision,
                  ready),
        precision,
    )
    h = _mm(layer_norm(x, p["ln2"]), p["mlp1"]["kernel"], precision, ready)
    h = jax.nn.gelu(_act(h + p["mlp1"]["bias"], precision))
    h = _mm(h, p["mlp2"]["kernel"], precision, ready) + p["mlp2"]["bias"]
    return _act(x + _act(h, precision), precision)


def embed(params, tokens):
    T = tokens.shape[1]
    return params["embed"][tokens] + params["pos_embed"][:, :T]


def head(params, x, precision: str = "float32"):
    x = layer_norm(x, params["ln_final"])
    if "head" in params:  # from round_weights
        return _mm(x, params["head"].T, precision, ready=True)
    return _mm(x, params["embed"].T, precision)


def forward(params, tokens, *, num_heads: int, depth: int,
            precision: str = "float32", remat: bool = False):
    """[B, T] int tokens -> [B, T, V] float32 logits. ``params`` may
    come from ``round_weights`` (it then holds ``head``)."""
    blk = functools.partial(
        block, num_heads=num_heads, precision=precision,
        ready="head" in params,
    )
    if remat:
        blk = jax.checkpoint(blk)
    x = _act(embed(params, tokens), precision)
    for i in range(1, depth + 1):
        x = blk(x, params[f"block{i}"])
    return head(params, x, precision)


def next_token_loss(logits, tokens):
    """Mean cross-entropy of position t predicting token t+1; the last
    position has no target."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return nll.mean()


def loss_fn(params, tokens, *, num_heads: int, depth: int,
            precision: str = "float32"):
    logits = forward(
        params, tokens, num_heads=num_heads, depth=depth,
        precision=precision, remat=True,
    )
    return next_token_loss(logits, tokens)


def loss_and_grad_rows(params, tokens, *, num_heads: int, depth: int,
                       precision: str = "float32", row_block: int = 1):
    """Loss and gradient of a whole batch, computed ``row_block`` rows
    at a time so the float32 activations of one block fit beside the
    parameters. Every row has T-1 targets, so the batch mean is the
    mean of the blocks' means."""
    n = tokens.shape[0]
    assert n % row_block == 0, (n, row_block)
    vg = jax.value_and_grad(functools.partial(
        loss_fn, num_heads=num_heads, depth=depth, precision=precision
    ))
    blocks = tokens.reshape(n // row_block, row_block, tokens.shape[1])
    if precision == "float32":
        used, pull = params, None
    else:  # round the weights once a step, not once a row block
        used, pull = jax.vjp(
            lambda p: round_weights(p, precision), params)

    def body(carry, toks):
        loss_acc, g_acc = carry
        loss, g = vg(used, toks)
        return (loss_acc + loss, jax.tree.map(jnp.add, g_acc, g)), None

    zero = jax.tree.map(jnp.zeros_like, used)
    (loss, g), _ = jax.lax.scan(body, (jnp.float32(0.0), zero), blocks)
    if pull is not None:
        (g,) = pull(g)
    k = n // row_block
    return loss / k, jax.tree.map(lambda x: x / k, g)


class AdamRef:
    """Plain Adam (Kingma & Ba 2015) with bias correction, float32:
    b1 0.9, b2 0.999, eps 1e-8, constant learning rate."""

    def __init__(self, lr: float, b1=0.9, b2=0.999, eps=1e-8):
        self.lr, self.b1, self.b2, self.eps = lr, b1, b2, eps

    def init(self, params):
        z = jax.tree.map(jnp.zeros_like, params)
        return {"mu": z, "nu": jax.tree.map(jnp.zeros_like, params),
                "count": jnp.zeros((), jnp.int32)}

    def update(self, params, grads, state):
        c = state["count"] + 1
        mu = jax.tree.map(
            lambda m, g: self.b1 * m + (1 - self.b1) * g,
            state["mu"], grads,
        )
        nu = jax.tree.map(
            lambda v, g: self.b2 * v + (1 - self.b2) * g * g,
            state["nu"], grads,
        )
        cf = c.astype(jnp.float32)
        mhat = 1.0 / (1 - self.b1 ** cf)
        vhat = 1.0 / (1 - self.b2 ** cf)
        new = jax.tree.map(
            lambda p, m, v: p - self.lr * (m * mhat)
            / (jnp.sqrt(v * vhat) + self.eps),
            params, mu, nu,
        )
        return new, {"mu": mu, "nu": nu, "count": c}
