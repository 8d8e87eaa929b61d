"""DeepSeek-V3 family (``model_type: "deepseek_v3"``; GigaChat3.1 is of
it): multi-head latent attention, a leading run of dense layers, then
expert layers with sigmoid-scored, group-limited routing and a shared
expert. Serving only: training it and serving it across chips are not
claimed (``partition_rules`` shards the experts over ``ep`` and is
untested across chips).

Pre-norm residual, as published (Liu et al. 2024, arXiv 2412.19437):
``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``.

**MLA.** ``c_q = RMSNorm(x W_dq)``; ``q = c_q W_uq`` -> per head
``[q_n (qk_nope); q_r (qk_rope)]``. ``[c; k_r] = x W_dkv``; ``c =
RMSNorm(c)``; ``k_r = RoPE(k_r)`` is ONE key a token, shared by all
heads; ``q_r = RoPE(q_r)``. ``[k_n; v] = c W_ukv`` per head. Scores
``(q_n k_n + q_r k_r) * (qk_nope + qk_rope)^-1/2 * m^2`` with YaRN's
``m`` (``ops.attention.yarn_mscale``). The rotary pairs are the two
HALVES of the rotary slice (``ops.attention.apply_rope``); the published
weights pair interleaved columns, which is the same function under a
column permutation of ``W_uq``'s and ``W_dkv``'s rotary columns.

**What is cached** is ``c`` (after its norm) and ``k_r`` (after RoPE):
``kv_lora_rank + qk_rope`` values a token a layer, stored as ONE leaf
``cached_latent`` padded to whole 128-lane tiles (576 -> 640: the chip
lays a minor dimension that is no multiple of 128 out of the way,
PERF.md). A decode tick runs ABSORBED: ``q~ = q_n W_uk^T`` (per head, to
the latent's width), scores ``[q~; q_r] . [c; k_r]``, values the frame's
first ``kv_lora_rank`` lanes, ``o = (sum p c) W_uv`` — every head reads
the one latent head, through the paged kernel in place. A prefill chunk
DECODES the row's cached latents to keys and values and takes the dense
path (fewer operations there); a served chunk gathers them from the
pool first, the bucket's frames (PERF.md §6, PR 30, has the timing of
both forms over the pool).

**FFN.** Dense SwiGLU in the first ``first_k_dense`` layers, which are
unrolled AHEAD of the scanned expert stack (``models/scan.py`` scans
one block class): their cache leaves are unstacked, beside the stack's
``[L, ...]`` ones, and the page pool carries both. Expert layers are
``ops.moe.MoEMLP`` (sigmoid scores in float32, selection bias, group
limit, gates normalised over all selected and scaled, one shared
expert), cut by ``experts_held`` to the range this chip holds; the
routed experts' tensors of all expert layers are ``StackedExperts``'
``experts/w_*`` leaves, broadcast to the loop and never sliced.

Not held: the multi-token-prediction module (a further stage; the
published forward does not run it).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from pytorch_distributed_tpu.models.llama import RMSNorm
from pytorch_distributed_tpu.ops.attention import (
    apply_rope,
    attention,
    decode_latent_cache,
    decode_positions,
    rope_frequencies,
    validate_write_pos,
    yarn_mscale,
)
from pytorch_distributed_tpu.ops.moe import MoEMLP, expert_params
from pytorch_distributed_tpu.runtime.precision import current_policy


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN context extension (``ops.attention.yarn_inverse_frequencies``).
    Frozen so configs stay hashable."""

    type: str = "yarn"
    factor: float = 40.0
    original_max_position_embeddings: int = 4_096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 129_280
    hidden_size: int = 7_168
    num_layers: int = 61
    first_k_dense: int = 3
    num_heads: int = 128
    q_lora_rank: int = 1_536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18_432      # the dense layers' SwiGLU
    moe_intermediate_size: int = 2_048   # one routed expert's
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    # the contiguous range of routed experts THIS chip holds, (first,
    # count); None = all of them. The router stays n_routed_experts wide.
    experts_held: Optional[Tuple[int, int]] = None
    max_seq_len: int = 163_840
    rope_theta: float = 10_000.0
    rope_scaling: Optional[YarnScaling] = YarnScaling()
    rms_eps: float = 1e-6
    kv_cache_quantize: Optional[str] = None
    remat: bool = False
    remat_policy: str = "full"

    def __post_init__(self):
        if self.kv_cache_quantize is not None:
            raise ValueError(
                "int8 latent pages are not supported: a latent frame "
                "holds a normed latent and a rotary key of different "
                f"ranges under one scale (kv_cache_quantize="
                f"{self.kv_cache_quantize!r}); serve this family with "
                "kv_cache_quantize=None"
            )
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError(
                f"first_k_dense {self.first_k_dense} must lie in "
                f"[0, num_layers={self.num_layers}]"
            )

    @property
    def latent_dim(self) -> int:
        """Values cached a token a layer: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_frame(self) -> int:
        """The cached frame's width: ``latent_dim`` in whole lane tiles."""
        return -(-self.latent_dim // 128) * 128

    @property
    def softmax_scale(self) -> float:
        scale = 1.0 / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)
        rs = self.rope_scaling
        if rs is not None:
            scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
        return scale

    @classmethod
    def tiny(cls, **kw) -> "DeepseekV3Config":
        base = dict(
            vocab_size=256, hidden_size=64, num_layers=3, first_k_dense=1,
            num_heads=4, q_lora_rank=32, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=16, num_experts_per_tok=4, n_group=4,
            topk_group=2, max_seq_len=128, rope_theta=10_000.0,
            rope_scaling=YarnScaling(
                factor=4.0, original_max_position_embeddings=32,
            ),
        )
        base.update(kw)
        return cls(**base)


class MLAttention(nn.Module):
    """Multi-head latent attention (module docstring)."""

    config: DeepseekV3Config

    @nn.compact
    def __call__(self, x, cos, sin, positions, write_pos, decode: bool,
                 cache_len: Optional[int]):
        cfg = self.config
        policy = current_policy()
        dense = lambda feats, name, axis=-1: nn.DenseGeneral(  # noqa: E731
            feats, axis=axis, use_bias=False, dtype=policy.compute_dtype,
            param_dtype=policy.param_dtype, name=name,
        )
        H, r = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        B, S, _ = x.shape
        c_q = RMSNorm(cfg.rms_eps, name="q_norm")(
            dense(cfg.q_lora_rank, "q_a")(x)
        )
        q = dense((H, dn + dr), "q_b")(c_q)                 # [B, S, H, dn+dr]
        q_n, q_r = q[..., :dn], apply_rope(q[..., dn:], cos, sin, positions)
        ckr = dense(r + dr, "kv_a")(x)                      # [B, S, r + dr]
        c = RMSNorm(cfg.rms_eps, name="kv_norm")(ckr[..., :r])
        k_r = apply_rope(ckr[..., None, r:], cos, sin, positions)  # 1 head
        w_ukv = self.param(
            "kv_b", nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2)),
            (r, H, dn + dv), policy.param_dtype,
        ).astype(policy.compute_dtype)
        w_uk, w_uv = w_ukv[..., :dn], w_ukv[..., dn:]

        def decoded(c_all, kr_all, **kw):
            """Keys and values decoded from latents ``[B, T, r]`` and
            rotary keys ``[B, T, 1, dr]``: the dense path."""
            k_n = jnp.einsum("btr,rhd->bthd", c_all, w_uk)
            v = jnp.einsum("btr,rhd->bthd", c_all, w_uv)
            k = jnp.concatenate([
                k_n, jnp.broadcast_to(kr_all, k_n.shape[:-1] + (dr,)),
            ], axis=-1)
            return attention(
                jnp.concatenate([q_n, q_r], axis=-1), k, v, causal=True,
                scale=cfg.softmax_scale, **kw,
            )

        if not decode:
            out = decoded(c, k_r)
        else:
            from pytorch_distributed_tpu.ops.paged_attention import (
                active_view,
                gathered_rows,
                is_chunk,
            )

            F = cfg.latent_frame
            frame = jnp.concatenate([
                c[:, :, None, :], k_r,
                jnp.zeros((B, S, 1, F - r - dr), c.dtype),
            ], axis=-1)                                     # [B, S, 1, F]
            k_all, v_all, offset = decode_latent_cache(
                self, frame, cache_len or cfg.max_seq_len, r,
                write_pos=write_pos,
            )
            paged = active_view() is not None
            if paged and is_chunk(S):
                # a served chunk: the row's bucket of frames out of the
                # pool, then the dense path's own arithmetic
                with gathered_rows(k_all) as rows:          # [B, T, F]
                    out = decoded(
                        rows[..., :r], rows[:, :, None, r:r + dr],
                        q_offset=offset,
                    )
            elif S == 1 or paged:
                # absorbed: every head reads the one latent head
                q_abs = jnp.einsum("bshd,rhd->bshr", q_n, w_uk)
                q_cat = jnp.concatenate([
                    q_abs, q_r, jnp.zeros((B, S, H, F - r - dr), q.dtype),
                ], axis=-1)
                o_lat = attention(
                    q_cat, k_all, v_all, causal=True, q_offset=offset,
                    scale=cfg.softmax_scale,
                )                                           # [B, S, H, r]
                out = jnp.einsum("bshr,rhd->bshd", o_lat, w_uv)
            else:
                out = decoded(
                    v_all[:, :, 0, :], k_all[..., r:r + dr], q_offset=offset,
                )
        return dense(cfg.hidden_size, "o", axis=(-2, -1))(out)


class DeepseekV3Block(nn.Module):
    """One decoder layer; ``dense_ffn`` picks the leading layers' plain
    SwiGLU over the expert layer."""

    config: DeepseekV3Config
    dense_ffn: bool = False

    @nn.compact
    def __call__(self, x, layer, cos, sin, positions, write_pos, experts,
                 decode: bool = False, cache_len: Optional[int] = None):
        cfg = self.config
        policy = current_policy()
        h = RMSNorm(cfg.rms_eps, name="attn_norm")(x)
        x = x + MLAttention(cfg, name="attn")(
            h, cos, sin, positions, write_pos, decode, cache_len
        )
        h = RMSNorm(cfg.rms_eps, name="mlp_norm")(x)
        if not self.dense_ffn:
            return x + MoEMLP(
                num_experts=cfg.n_routed_experts,
                d_ff=cfg.moe_intermediate_size,
                k=cfg.num_experts_per_tok, capacity_factor=None,
                activation="swiglu", scoring="sigmoid", select_bias=True,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                routed_scale=cfg.routed_scaling_factor,
                shared_d_ff=(
                    cfg.moe_intermediate_size * cfg.n_shared_experts or None
                ),
                held=cfg.experts_held, name="moe",
            )(h, experts, layer)
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=policy.compute_dtype,
            param_dtype=policy.param_dtype, name=name,
        )
        gate = dense(cfg.intermediate_size, "gate")(h)
        up = dense(cfg.intermediate_size, "up")(h)
        return x + dense(cfg.hidden_size, "down")(nn.silu(gate) * up)


class StackedExperts(nn.Module):
    """The routed experts' tensors of EVERY expert layer, ``[L, held, ..]``,
    declared beside the scanned stack and handed to it whole: the layer
    loop broadcasts them and each layer's grouped product reads its own
    plane in place. As leaves of the scanned block they would be sliced
    a layer, and a kernel's operand sliced out of a stacked leaf is a
    copy — 2.1 GB an expert layer at the published widths, twice the
    kernel's own time (PERF.md, PR 27)."""

    config: DeepseekV3Config

    @nn.compact
    def __call__(self):
        cfg = self.config
        held = (cfg.experts_held or (0, cfg.n_routed_experts))[1]
        w_in, w_gate, w_out = expert_params(
            self, held, cfg.hidden_size, cfg.moe_intermediate_size, True,
            lead=(cfg.num_layers - cfg.first_k_dense,),
        )
        return {"w_in": w_in, "w_gate": w_gate, "w_out": w_out}


class DeepseekV3ForCausalLM(nn.Module):
    """Returns [B, S, vocab] logits; untied head. The decode contract is
    the Llama body's (``positions``, ``write_pos``, ``cache_len``), so
    ``generate`` and the ``ServeEngine`` drive it unchanged."""

    config: DeepseekV3Config

    @nn.compact
    def __call__(
        self,
        input_ids,
        positions: Optional[jnp.ndarray] = None,
        *,
        write_pos: Optional[jnp.ndarray] = None,
        train: bool = False,
        decode: bool = False,
        cache_len: Optional[int] = None,
    ):
        del train  # no dropout anywhere in this family
        cfg = self.config
        policy = current_policy()
        B, S = input_ids.shape
        if cache_len is not None and cache_len > cfg.max_seq_len:
            raise ValueError(
                f"cache_len {cache_len} > max_seq_len {cfg.max_seq_len}"
            )
        validate_write_pos(write_pos, decode, positions)
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, param_dtype=policy.param_dtype,
            dtype=policy.compute_dtype, name="embed",
        )(input_ids)
        if decode:
            table_len = cache_len or cfg.max_seq_len
        else:
            table_len = S if positions is None else cfg.max_seq_len
        cos, sin = rope_frequencies(
            cfg.qk_rope_head_dim, table_len, cfg.rope_theta,
            scaling=cfg.rope_scaling,
        )
        if decode:
            auto = jnp.broadcast_to(
                decode_positions(self, S)[None, :], (B, S)
            )
            if positions is None:
                positions = auto
        # the leading dense layers, unrolled: one block class a scan
        for i in range(cfg.first_k_dense):
            x = DeepseekV3Block(cfg, dense_ffn=True, name=f"dense{i}")(
                x, None, cos, sin, positions, write_pos, None, decode,
                cache_len,
            )
        if cfg.num_layers > cfg.first_k_dense:
            from pytorch_distributed_tpu.models.scan import scan_stack

            x = scan_stack(
                DeepseekV3Block, cfg, static_argnums=(7, 8), name="layers",
                length=cfg.num_layers - cfg.first_k_dense, with_layer=True,
            )(x, cos, sin, positions, write_pos,
              StackedExperts(cfg, name="experts")(), decode, cache_len)
        x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=policy.compute_dtype,
            param_dtype=policy.param_dtype, name="lm_head",
        )(x)
        return policy.to_output(logits)


def deepseek_v3_partition_rules(ep_axis: str = "ep", tp_axis: str = "tp"):
    """The experts over ``ep`` (each expert's hidden over ``tp``), the
    attention up-projections and the output projection over heads, the
    dense layers' SwiGLU Megatron-style; the low-rank down-projections
    and the router replicated. UNTESTED across chips: no exchange of
    tokens between expert ranks exists yet (ROADMAP B1)."""
    from jax.sharding import PartitionSpec as P

    from pytorch_distributed_tpu.ops.moe import moe_partition_rules
    from pytorch_distributed_tpu.parallel.sharding import stacked

    attn = [
        (r"attn/q_b/kernel", P(None, tp_axis, None)),
        (r"attn/kv_b", P(None, tp_axis, None)),
        (r"attn/o/kernel", P(tp_axis, None, None)),
    ]
    rules = [
        (rf"dense\d+/{name}", spec) for name, spec in attn + [
            (r"(gate|up)/kernel", P(None, tp_axis)),
            (r"down/kernel", P(tp_axis, None)),
        ]
    ]
    rules += [(rf"layers/block/{name}", stacked(spec)) for name, spec in attn]
    rules += [
        (rf"(/moe|experts)/{name}", stacked(spec))
        for name, spec in moe_partition_rules(ep_axis, tp_axis) + [
            (r"shared_(gate|up)/kernel", P(None, tp_axis)),
            (r"shared_down/kernel", P(tp_axis, None)),
        ]
    ]
    return rules + [
        (r"embed/embedding", P(None, tp_axis)),
        (r"lm_head/kernel", P(None, tp_axis)),
    ]
