"""Mistral-7B's decoder in plain ``jax.numpy``, one layer at a time.

Follows Jiang et al. 2023 and the published ``Mistral-7B-v0.1`` config:
RMSNorm, rotary positions applied to the two halves of each head
(``rotate_half``, theta 1e4), grouped-query attention (each KV head
serves ``heads / kv_heads`` query heads), a causal sliding window in
which position i sees keys in (i - window, i], SwiGLU, an untied head.
float32 throughout, every matmul at ``highest`` precision; no kernels,
no cache, no batching. Nothing of the program is imported. The weights'
layout follows the tree the benchmark makes
(``perfbench/families/mistral.py``): projections are ``[D, heads, hd]``
and the output projection ``[heads, hd, D]``.

The model never sits in memory whole: ``layer`` takes one layer's
leaves, and the caller makes them from the seed just before.
"""

import math

import jax
import jax.numpy as jnp

from perfbench.references.common import make_einsum


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * scale


def rope(x, positions, theta):
    """Rotate ``x [S, H, hd]`` by position (half-split convention)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def embed(top, ids):
    return top["embed/embedding"].astype(jnp.float32)[ids]


def layer(w, x, *, theta, eps, window, precision="float32"):
    """One decoder layer on one sequence ``x [S, D]``."""
    einsum = make_einsum(precision)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    S = x.shape[0]
    pos = jnp.arange(S)
    h = rms_norm(x, w["attn_norm/scale"], eps)
    q = rope(einsum("sd,dhe->she", h, w["q/kernel"]), pos, theta)
    k = rope(einsum("sd,dke->ske", h, w["k/kernel"]), pos, theta)
    v = einsum("sd,dke->ske", h, w["v/kernel"])
    H, K, hd = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(S, K, H // K, hd)
    scores = einsum("skge,tke->kgst", qg, k) / math.sqrt(hd)
    dist = pos[:, None] - pos[None, :]
    keep = dist >= 0
    if window is not None:
        keep = keep & (dist < window)
    scores = jnp.where(keep, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = einsum("kgst,tke->skge", probs, v).reshape(S, H, hd)
    x = x + einsum("she,hed->sd", attn, w["o/kernel"])
    h = rms_norm(x, w["mlp_norm/scale"], eps)
    gate = einsum("sd,df->sf", h, w["gate/kernel"])
    up = einsum("sd,df->sf", h, w["up/kernel"])
    return x + einsum("sf,fd->sd", jax.nn.silu(gate) * up, w["down/kernel"])


def head(top, x, *, eps, precision="float32"):
    """Final norm and the untied head on ``x [S, D]`` -> ``[S, V]``."""
    einsum = make_einsum(precision)
    x = rms_norm(x, top["final_norm/scale"].astype(jnp.float32), eps)
    return einsum("sd,dv->sv", x, top["lm_head/kernel"].astype(jnp.float32))


_layer = jax.jit(layer, static_argnames=("theta", "eps", "window",
                                         "precision"))
_head = jax.jit(head, static_argnames=("eps", "precision"))


def served_logits(cfg, weights, ids, start, precision="float32"):
    """Logits ``[len(ids) - start, V]`` of positions ``start..`` of ONE
    sequence, the model walked layer by layer: ``weights.layer(l)`` makes
    one layer's leaves from the seed just before they are used. The
    sequence is padded to a multiple of 256 (causal attention: padding
    behind a position cannot reach it) so few shapes are compiled."""
    import numpy as np

    S = len(ids)
    pad = -(-S // 256) * 256
    padded = np.zeros(pad, np.int32)
    padded[:S] = ids
    top = weights.top()
    x = embed(top, jnp.asarray(padded))
    for l in range(cfg["num_hidden_layers"]):
        x = _layer(
            weights.layer(l), x, theta=cfg["rope_theta"],
            eps=cfg["rms_norm_eps"], window=cfg["sliding_window"],
            precision=precision,
        )
    # the head only where tokens were served (padded to 64 rows)
    n = S - start
    rows = -(-n // 64) * 64
    begin = min(start, pad - rows)
    out = _head(
        top, jax.lax.dynamic_slice_in_dim(x, begin, rows, 0),
        eps=cfg["rms_norm_eps"], precision=precision,
    )
    return out[start - begin: start - begin + n]
