"""GigaChat3.1-702B-A36B's decoder (``model_type: "deepseek_v3"``) in
plain ``jax.numpy``, one layer at a time, cut to what one chip of the
deployment holds.

Follows DeepSeek-V3 (Liu et al. 2024, arXiv 2412.19437) and the
published ``GigaChat3.1-702B-A36B`` config. Pre-norm residual,
``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``.

*MLA.* ``c_q = RMSNorm(x W_dq)``; ``q = c_q W_uq`` -> 64 heads of
``[q_n (128); q_r (64)]``. ``[c; k_r] = x W_dkv`` (512; 64);
``c = RMSNorm(c)``; ``k_r = RoPE(k_r)``, one a token, shared by all heads;
``q_r = RoPE(q_r)``. ``[k_n; v] = c W_ukv`` per head (128; 192). Scores
``(q_n k_n + q_r k_r) * 192^-1/2 * m^2``, ``m = 0.1 ln 64 + 1`` (YaRN,
``mscale_all_dim`` 1; the factor on cos and sin, ``mscale /
mscale_all_dim``, is 1); causal softmax; ``concat_h(sum p v) W_o``.
Every key and value is decoded from its latent and every head attends
them directly: no cache and no absorption here.

*RoPE.* YaRN frequencies (theta 1e5, factor 64, original window 4096,
``beta_fast`` 32, ``beta_slow`` 1) over INTERLEAVED pairs: columns
``(2j, 2j + 1)`` of a rotary slice turn by ``pos * f_j``, as published.
The program rotates the two halves of the slice, columns ``(j, j + 32)``;
it is given the same weight leaves, so the reference reads its rotary
columns through the permutation ``rotary_columns``: published column
``2j`` is the program's ``j`` and ``2j + 1`` its ``j + 32``. Under that
permutation of ``W_uq``'s and ``W_dkv``'s rotary columns the two are the
same function (a dot product is blind to a permutation both sides share).

*FFN.* Dense SwiGLU (18432) in the leading layer. Otherwise ``s =
sigmoid(x W_g)`` in float32 over the published 256 experts; selection on
``s' = s + b``: a group's score is the sum of its two largest ``s'`` (8
groups of 32), the 4 best groups are kept, the 8 largest ``s'`` among
them are selected (``lax.top_k``: a tie goes to the lower index);
weights ``w = 2.5 * s_e / sum_selected s`` — the sum over all 8, held
here or not; ``FFN(x) = sum_{selected, held} w_e SwiGLU_e(x) +
SwiGLU_shared(x)``. The experts held are ``n_routed_experts`` from
``deployment_rank * n_routed_experts`` on; what the absent experts
would add is left out, as in the program (the deployment's other chips
add it). Every token goes through every held expert and is weighted by
nought where it was not selected.

float32 throughout, every matmul at ``highest`` precision; no kernels,
no cache, no batching; attention in blocks of queries so that 8192
positions fit. Nothing of the program is imported. The weights' layout
follows the tree the benchmark makes (``perfbench/families/
deepseek_v3.py``). The model never sits in memory whole: ``layer``
takes one layer's leaves, and the caller makes them from the seed just
before. Not held: the multi-token-prediction module (the published
forward does not run it).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.references.common import make_einsum

QUERY_BLOCK = 256
# a sequence is padded to whole PAD_TO positions: each padded length
# compiles a layer anew (sixteen experts unrolled, at ``highest``), and
# over a few runs every length is in the compile cache
PAD_TO = 1024


def rotary_columns(dr):
    """Published (interleaved) rotary column -> the program's column."""
    perm = np.empty(dr, np.int64)
    perm[0::2] = np.arange(dr // 2)
    perm[1::2] = np.arange(dr // 2) + dr // 2
    return perm


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * scale


def yarn_inverse_frequencies(dr, theta, rs):
    """One frequency a rotary pair: pairs that turn more than
    ``beta_fast`` times in the original window keep ``theta^(-2j/dr)``,
    pairs that turn fewer than ``beta_slow`` times are slowed by
    ``factor``, a linear ramp over the pair index between."""
    j = jnp.arange(dr // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * j / dr)

    def pair_that_turns(n):
        return dr * math.log(
            rs["original_max_position_embeddings"] / (n * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(rs["beta_slow"])), dr - 1)
    if low == high:
        high += 0.001
    slowed_share = jnp.clip((j - low) / (high - low), 0.0, 1.0)
    return plain / rs["factor"] * slowed_share + plain * (1 - slowed_share)


def yarn_mscale(rs):
    f = rs["factor"]
    return 1.0 if f <= 1 else 0.1 * rs["mscale_all_dim"] * math.log(f) + 1.0


def rope_interleaved(x, positions, inv_freq):
    """Rotate pairs ``(2j, 2j + 1)`` of ``x [S, H, dr]`` by position."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def embed(top, ids):
    return top["embed/embedding"].astype(jnp.float32)[ids]


def mla(w, x, cfg, einsum):
    """Multi-head latent attention on one sequence ``x [S, D]`` (already
    normed); ``w`` holds the ``attn/*`` leaves."""
    f32 = lambda k: w[k].astype(jnp.float32)  # noqa: E731
    S = x.shape[0]
    r, dn, dr = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                 cfg["qk_rope_head_dim"])
    perm = rotary_columns(dr)
    pos = jnp.arange(S)
    inv = yarn_inverse_frequencies(dr, float(cfg["rope_theta"]),
                                   cfg["rope_scaling"])
    eps = cfg["rms_norm_eps"]
    c_q = rms_norm(einsum("sd,dr->sr", x, f32("attn/q_a/kernel")),
                   f32("attn/q_norm/scale"), eps)
    q = einsum("sr,rhe->she", c_q, f32("attn/q_b/kernel"))
    q_n = q[..., :dn]
    q_r = rope_interleaved(q[..., dn:][..., perm], pos, inv)
    ckr = einsum("sd,de->se", x, f32("attn/kv_a/kernel"))
    c = rms_norm(ckr[:, :r], f32("attn/kv_norm/scale"), eps)
    k_r = rope_interleaved(ckr[:, None, r:][..., perm], pos, inv)[:, 0]
    kv = einsum("sr,rhe->she", c, f32("attn/kv_b"))
    k_n, v = kv[..., :dn], kv[..., dn:]
    softmax_scale = yarn_mscale(cfg["rope_scaling"]) ** 2 / math.sqrt(dn + dr)

    def block(start):
        """Queries ``[start, start + QUERY_BLOCK)`` against every key."""
        qn = jax.lax.dynamic_slice_in_dim(q_n, start, QUERY_BLOCK, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_r, start, QUERY_BLOCK, 0)
        scores = (
            einsum("she,the->hst", qn, k_n) + einsum("she,te->hst", qr, k_r)
        ) * softmax_scale
        keep = (start + jnp.arange(QUERY_BLOCK))[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return einsum("hst,the->she", probs, v)

    if S % QUERY_BLOCK:
        raise ValueError(f"pad the sequence to a multiple of {QUERY_BLOCK}")
    out = jax.lax.map(block, jnp.arange(0, S, QUERY_BLOCK))
    out = out.reshape((S,) + out.shape[2:])
    return einsum("she,hed->sd", out, f32("attn/o/kernel"))


def swiglu(x, gate, up, down, einsum):
    g = einsum("sd,df->sf", x, gate)
    u = einsum("sd,df->sf", x, up)
    return einsum("sf,fd->sd", jax.nn.silu(g) * u, down)


def route(scores, bias, cfg):
    """``(weights [S, E], selected [S, E] bool)`` over the published
    router width: the weight of a selected expert, nought elsewhere."""
    S, E = scores.shape
    G, kept, k = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    choice = scores + bias[None, :]
    groups = choice.reshape(S, G, E // G)
    group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)     # [S, G]
    _, best = jax.lax.top_k(group_score, kept)
    in_kept = jnp.zeros((S, G), bool).at[
        jnp.arange(S)[:, None], best
    ].set(True)
    choice = jnp.where(jnp.repeat(in_kept, E // G, axis=1), choice, 0.0)
    _, chosen = jax.lax.top_k(choice, k)                             # [S, k]
    selected = jnp.zeros((S, E), bool).at[
        jnp.arange(S)[:, None], chosen
    ].set(True)
    picked = jnp.where(selected, scores, 0.0)
    total = jnp.sum(picked, axis=-1, keepdims=True)
    return cfg["routed_scaling_factor"] * picked / total, selected


def experts(w, x, cfg, einsum):
    """The expert layer's part this chip gives, on ``x [S, D]``."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    logits = einsum("sd,de->se", x, f32(w["moe/router/kernel"]))
    weights, _ = route(
        jax.nn.sigmoid(logits), f32(w["moe/router_bias"]), cfg
    )
    n = w["moe/w_in"].shape[0]
    first = cfg.get("deployment_rank", 0) * n
    out = swiglu(
        x, f32(w["moe/shared_gate/kernel"]), f32(w["moe/shared_up/kernel"]),
        f32(w["moe/shared_down/kernel"]), einsum,
    )
    for e in range(n):  # every token through every held expert
        y = swiglu(x, f32(w["moe/w_gate"][e]), f32(w["moe/w_in"][e]),
                   f32(w["moe/w_out"][e]), einsum)
        out = out + weights[:, first + e, None] * y
    return out


def layer(w, x, cfg, *, dense, precision="float32"):
    """One decoder layer on one sequence ``x [S, D]``; ``dense`` picks
    the leading layer's plain SwiGLU."""
    einsum = make_einsum(precision)
    eps = cfg["rms_norm_eps"]
    f32 = lambda k: w[k].astype(jnp.float32)  # noqa: E731
    h = rms_norm(x, f32("attn_norm/scale"), eps)
    x = x + mla(w, h, cfg, einsum)
    h = rms_norm(x, f32("mlp_norm/scale"), eps)
    if dense:
        return x + swiglu(h, f32("gate/kernel"), f32("up/kernel"),
                          f32("down/kernel"), einsum)
    return x + experts(w, h, cfg, einsum)


def head(top, x, *, eps, precision="float32"):
    """Final norm and the untied head on ``x [S, D]`` -> ``[S, V]``."""
    einsum = make_einsum(precision)
    x = rms_norm(x, top["final_norm/scale"].astype(jnp.float32), eps)
    return einsum("sd,dv->sv", x, top["lm_head/kernel"].astype(jnp.float32))


_KEYS = (
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "rope_theta",
    "rope_scaling", "rms_norm_eps", "n_group", "topk_group",
    "num_experts_per_tok", "routed_scaling_factor", "deployment_rank",
)


def _static(cfg):
    """What a layer reads of the configuration, hashable: a static
    argument of the jitted layer."""
    return tuple(
        (k, tuple(sorted(cfg[k].items())) if isinstance(cfg[k], dict)
         else cfg[k])
        for k in _KEYS if k in cfg
    )


def _layer(w, x, cfg_static, dense, precision):
    cfg = {k: dict(v) if isinstance(v, tuple) else v for k, v in cfg_static}
    return layer(w, x, cfg, dense=dense, precision=precision)


_layer_jit = jax.jit(_layer, static_argnames=("cfg_static", "dense",
                                              "precision"))
_head = jax.jit(head, static_argnames=("eps", "precision"))


def served_logits(cfg, weights, ids, start, precision="float32"):
    """Logits ``[len(ids) - start, V]`` of positions ``start..`` of ONE
    sequence, the model walked layer by layer: the leading dense layer's
    leaves are top leaves under ``dense0/`` and the routed experts' are
    the top leaves ``experts/w_*`` (``[L, held, ..]``); ``weights.layer(l)``
    makes the rest of expert layer ``l`` from the seed just before use. The
    sequence is padded to a multiple of ``PAD_TO`` (causal attention: padding
    behind a position cannot reach it) so few shapes are compiled."""
    S = len(ids)
    pad = -(-S // PAD_TO) * PAD_TO
    padded = np.zeros(pad, np.int32)
    padded[:S] = ids
    top = weights.top()
    static = _static(cfg)
    x = embed(top, jnp.asarray(padded))
    lead = {k[len("dense0/"):]: v for k, v in top.items()
            if k.startswith("dense0/")}
    x = _layer_jit(lead, x, static, True, precision)
    for l in range(cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]):
        w = dict(weights.layer(l))
        # the routed experts of all expert layers are top leaves
        # [L, held, ..]: this layer's plane
        w.update({f"moe/{k}": top[f"experts/{k}"][l]
                  for k in ("w_gate", "w_in", "w_out")})
        x = _layer_jit(w, x, static, False, precision)
    # the head only where tokens were served (padded to 64 rows)
    n = S - start
    rows = -(-n // 64) * 64
    begin = max(min(start, pad - rows), 0)
    out = _head(
        top, jax.lax.dynamic_slice_in_dim(x, begin, min(rows, pad), 0),
        eps=cfg["rms_norm_eps"], precision=precision,
    )
    return out[start - begin: start - begin + n]
