"""GPT-2 in plain ``jax.numpy``: forward, loss, gradients and AdamW.

Follows Radford et al. 2019 and the published ``gpt2-medium`` config:
learned positions, pre-LayerNorm blocks, fused q/k/v projection, causal
softmax attention scaled by 1/sqrt(head_dim), a 4x MLP with the tanh
approximation of GELU (``gelu_new``), a final LayerNorm and a head tied
to the token embedding. float32 throughout, every matmul at ``highest``
precision; no kernels, no cache, no batching tricks. Nothing of the
program is imported. The only departure from the description is the
layout of the weights, which follows the tree the benchmark makes
(``perfbench/families/gpt2.py``): q/k/v are one ``[D, 3, H, hd]`` kernel
and the output projection is ``[H, hd, D]``.

The layers run under ``lax.scan`` over their stacked weights so that
the program compiles in seconds; a scan is a loop, not a kernel. Each
block is recomputed in the backward pass (``jax.checkpoint``), which
changes memory and not one number.
"""

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.references.common import make_einsum


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)
    ))


def block(w, x, eps, einsum):
    """One pre-LN block on ``x [B, S, D]``; ``w`` is one layer's leaves."""
    S = x.shape[1]
    hd = w["attn_qkv/kernel"].shape[-1]
    h = layer_norm(x, w["ln1/scale"], w["ln1/bias"], eps)
    qkv = einsum("bsd,dthe->bsthe", h, w["attn_qkv/kernel"])
    qkv = qkv + w["attn_qkv/bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = einsum("bshe,bthe->bhst", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = einsum("bhst,bthe->bshe", probs, v)
    attn = einsum("bshe,hed->bsd", attn, w["attn_out/kernel"])
    x = x + attn + w["attn_out/bias"]
    h = layer_norm(x, w["ln2/scale"], w["ln2/bias"], eps)
    h = einsum("bsd,df->bsf", h, w["mlp_up/kernel"]) + w["mlp_up/bias"]
    h = gelu_new(h)
    h = einsum("bsf,fd->bsd", h, w["mlp_down/kernel"]) + w["mlp_down/bias"]
    return x + h


def logits(top, layers, ids, eps, precision="float32"):
    """``[B, S, V]`` float32 logits. ``top`` holds the leaves outside
    the blocks, ``layers`` every block's leaves stacked on ``[L]``."""
    einsum = make_einsum(precision)
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t
    )
    top, layers = f32(top), f32(layers)
    S = ids.shape[1]
    x = top["wte/embedding"][ids] + top["wpe/embedding"][jnp.arange(S)]

    # each block's activations are recomputed in the backward pass
    # (jax.checkpoint): the same mathematics in a twelfth of the memory,
    # which is what lets float32 gradients of 24 layers fit on the chip
    @jax.checkpoint
    def body(x, w):
        return block(w, x, eps, einsum), None

    x, _ = jax.lax.scan(body, x, layers)
    x = layer_norm(x, top["ln_f/scale"], top["ln_f/bias"], eps)
    return einsum("bsd,vd->bsv", x, top["wte/embedding"])


def token_losses(top, layers, ids, eps, precision="float32"):
    """Next-token cross-entropy of every position but the last, ``[B, S-1]``."""
    lg = logits(top, layers, ids, eps, precision)[:, :-1]
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return logz - picked


def loss_and_grads(top, layers, ids, eps, precision="float32",
                   rows_per_block=1):
    """Mean token loss of the batch and its gradient, in blocks of rows
    so that the activations of a float32 backward pass fit."""
    n_tok = ids.shape[0] * (ids.shape[1] - 1)

    def block_sum(params, rows):
        t, l = params
        return jnp.sum(token_losses(t, l, rows, eps, precision))

    vg = jax.jit(jax.value_and_grad(block_sum))
    total, grads = 0.0, None
    for i in range(0, ids.shape[0], rows_per_block):
        val, g = vg((top, layers), ids[i:i + rows_per_block])
        total = total + val
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g
        )
    scale = 1.0 / n_tok
    return total * scale, jax.tree_util.tree_map(
        lambda g: g * scale, grads
    )


def global_norm(tree):
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(tree)
    ))


@jax.jit
def clip_by_global_norm(grads, max_norm):
    norm = global_norm(grads)
    factor = jnp.where(norm > max_norm, max_norm / norm, 1.0)
    return jax.tree_util.tree_map(lambda g: g * factor, grads)


@jax.jit
def adamw(params, grads, mu, nu, count, lr, b1, b2, eps, weight_decay):
    """One AdamW step (Loshchilov & Hutter): decay is applied to every
    leaf, as ``optax.adamw`` does without a mask."""
    count = count + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(
        lambda v, g: b2 * v + (1 - b2) * jnp.square(g), nu, grads
    )
    c1 = 1 - b1 ** count
    c2 = 1 - b2 ** count

    def upd(p, m, v):
        return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                         + weight_decay * p)

    return jax.tree_util.tree_map(upd, params, mu, nu), mu, nu, count


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _sequence_logits(top, layers, ids, eps, precision):
    return logits(top, layers, ids, eps, precision)[0]


def served_logits(cfg, weights, ids, start, precision="float32"):
    """Logits ``[len(ids) - start, V]`` of positions ``start..`` of ONE
    sequence: what a served model's tokens are compared with.
    ``weights`` gives ``top()`` and ``stacked()`` from the seed. The
    sequence is padded to a multiple of 64 (causal attention: padding
    behind a position cannot reach it) so few shapes are compiled."""
    import numpy as np

    S = len(ids)
    pad = min(-(-S // 64) * 64, cfg["n_positions"])
    padded = np.zeros((1, pad), np.int32)
    padded[0, :S] = ids
    out = _sequence_logits(
        weights.top(), weights.stacked(), padded,
        cfg["layer_norm_epsilon"], precision,
    )
    return out[start:S]
