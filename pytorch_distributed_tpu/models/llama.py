"""Llama-3 — recipe 5, the stretch goal (BASELINE.json:11:
"Llama-3-8B, FSDP full-shard -> XLA SPMD").

Decoder with RMSNorm, rotary positions (theta 500k), grouped-query
attention (32 q / 8 kv heads at 8B) and SwiGLU MLP. Sequence length is an
explicit axis everywhere so the sequence-parallel strategies
(parallel/sequence.py) can shard it; ``positions`` plumb through to RoPE
for mid-sequence shards.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from pytorch_distributed_tpu.ops.attention import (
    apply_rope,
    attention,
    rope_frequencies,
    validate_write_pos,
)
from pytorch_distributed_tpu.runtime.precision import current_policy


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """Context-window extension for RoPE (ops/attention.py
    ``rope_frequencies``). ``type``: "linear" (position interpolation)
    or "llama3" (HF Llama-3.1 frequency-dependent scheme). Frozen so
    configs stay hashable."""

    type: str = "llama3"
    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8_192


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    hidden_size: int = 4_096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 14_336
    max_seq_len: int = 8_192
    rope_theta: float = 500_000.0
    rms_eps: float = 1e-5
    # sliding-window (Mistral) attention: position i sees keys in
    # (i - window, i] only; None = full causal (Llama)
    sliding_window: Optional[int] = None
    # context-window extension (Llama-3.1 long context): None = plain RoPE
    rope_scaling: Optional[RopeScaling] = None
    # biases on the q/k/v projections (Qwen2); o/gate/up/down never
    # carry biases in any Llama-body family
    attention_bias: bool = False
    # share the embedding table with the LM head (Llama-3.2-1B/3B,
    # Qwen2-0.5B/1.5B, Gemma); False = the untied Llama-3 layout
    tie_word_embeddings: bool = False
    # Gemma-isms, all defaulting to the Llama behavior:
    # explicit per-head dim (Gemma: 256, decoupled from hidden/heads)
    override_head_dim: Optional[int] = None
    # RMSNorm multiplies by (1 + scale) — zero-centered scale init
    rms_offset: bool = False
    # FFN gate activation: silu (Llama/Mistral/Qwen) | gelu (Gemma's
    # tanh-approximate gelu_pytorch_tanh)
    hidden_act: str = "silu"
    # multiply embeddings by sqrt(hidden_size) after lookup
    scale_embedding: bool = False
    # "int8" stores the decode KV cache quantized (~2x less HBM than a
    # bf16 cache, ~4x than f32 — the long-context serving ceiling);
    # None = exact bf16/f32.
    # Lossy: greedy decode agrees with the exact cache on most tokens
    # but is not bitwise identical.
    kv_cache_quantize: Optional[str] = None
    # per-head RMSNorm on q and k before RoPE (Qwen3 / OLMo-2 /
    # Gemma-3 idiom) — stabilizes attention logits at scale
    qk_norm: bool = False
    # scan over layers (models/scan.py): one compiled block, [L, ...]
    # stacked params. False restores the unrolled per-layer tree.
    scan_layers: bool = True
    remat: bool = False  # recompute block activations in backward
    scan_dequant: bool = False  # per-layer dequant of quantized block params
    # inside the scan (models/scan.py) — the single-chip big-model serving path

    remat_policy: str = "full"  # full | dots | dots_no_batch (models/scan.py)

    def __post_init__(self):
        if self.scan_dequant and not self.scan_layers:
            raise ValueError(
                "scan_dequant dequantizes inside the layer scan — it "
                "requires scan_layers=True (an unrolled stack would hand "
                "raw quantized dicts to the blocks)"
            )
        if self.hidden_act not in ("silu", "gelu"):
            raise ValueError(
                f"hidden_act must be 'silu' or 'gelu', got "
                f"{self.hidden_act!r}"
            )
        if self.kv_cache_quantize not in (None, "int8"):
            raise ValueError(
                f"kv_cache_quantize must be None or 'int8', got "
                f"{self.kv_cache_quantize!r}"
            )

    @property
    def head_dim(self) -> int:
        if self.override_head_dim is not None:
            return self.override_head_dim
        return self.hidden_size // self.num_heads

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def llama3_1_8b(cls) -> "LlamaConfig":
        """Llama-3.1-8B: the 3.0 geometry + llama3 rope scaling to 128k.
        Serve long contexts with an explicit ``cache_len`` — a
        max_seq_len-sized KV cache is ~16 GB at 128k."""
        return cls(
            max_seq_len=131_072,
            rope_scaling=RopeScaling(
                type="llama3", factor=8.0, low_freq_factor=1.0,
                high_freq_factor=4.0,
                original_max_position_embeddings=8_192,
            ),
        )

    @classmethod
    def llama3_2_1b(cls) -> "LlamaConfig":
        """Llama-3.2-1B: tied embeddings + factor-32 llama3 scaling."""
        return cls(
            hidden_size=2_048, num_layers=16, num_heads=32,
            num_kv_heads=8, intermediate_size=8_192,
            max_seq_len=131_072, tie_word_embeddings=True,
            rope_scaling=RopeScaling(
                type="llama3", factor=32.0, low_freq_factor=1.0,
                high_freq_factor=4.0,
                original_max_position_embeddings=8_192,
            ),
        )

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        return cls(
            vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, intermediate_size=128, max_seq_len=128,
        )


class RMSNorm(nn.Module):
    eps: float = 1e-5
    # Gemma stores a ZERO-centered scale and multiplies by (1 + scale);
    # init stays zeros so a fresh tied-Gemma init is the identity norm
    offset: bool = False

    @nn.compact
    def __call__(self, x):
        policy = current_policy()
        scale = self.param(
            "scale",
            nn.initializers.zeros if self.offset else nn.initializers.ones,
            (x.shape[-1],), policy.param_dtype,
        )
        x32 = x.astype(jnp.float32)
        rms = jnp.sqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        mult = scale.astype(jnp.float32)
        if self.offset:
            mult = 1.0 + mult
        return (x32 / rms * mult).astype(x.dtype)


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin, positions, segment_ids, kv_mask,
                 write_pos, deterministic: bool, decode: bool = False,
                 cache_len: Optional[int] = None):
        cfg = self.config
        policy = current_policy()
        dense = lambda feats, name, axis=-1, use_bias=False: (  # noqa: E731
            nn.DenseGeneral(
                feats, axis=axis, use_bias=use_bias,
                dtype=policy.compute_dtype,
                param_dtype=policy.param_dtype, name=name,
            )
        )
        h = RMSNorm(cfg.rms_eps, cfg.rms_offset, name="attn_norm")(x)
        ab = cfg.attention_bias
        q = dense((cfg.num_heads, cfg.head_dim), "q", use_bias=ab)(h)
        k = dense((cfg.num_kv_heads, cfg.head_dim), "k", use_bias=ab)(h)
        v = dense((cfg.num_kv_heads, cfg.head_dim), "v", use_bias=ab)(h)
        if cfg.qk_norm:
            # per-head RMSNorm over head_dim, BEFORE rotary (Qwen3's
            # q_norm/k_norm: one [head_dim] scale shared across heads)
            q = RMSNorm(cfg.rms_eps, cfg.rms_offset, name="q_norm")(q)
            k = RMSNorm(cfg.rms_eps, cfg.rms_offset, name="k_norm")(k)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        if decode:
            from pytorch_distributed_tpu.ops.attention import decode_cache

            k, v, offset = decode_cache(
                self, k, v, cache_len or cfg.max_seq_len,
                quantize=cfg.kv_cache_quantize, write_pos=write_pos,
            )
            attn = attention(
                q, k, v, causal=True, q_offset=offset, mask=kv_mask,
                window=cfg.sliding_window,
            )
        else:
            attn = attention(
                q, k, v, causal=True, segment_ids=segment_ids,
                window=cfg.sliding_window,
            )
        attn = dense(cfg.hidden_size, "o", axis=(-2, -1))(attn)
        x = x + attn

        h = RMSNorm(cfg.rms_eps, cfg.rms_offset, name="mlp_norm")(x)
        return x + self._ffn(h, dense)

    def _ffn(self, h, dense):
        """Gated MLP — the one piece variant decoders override (the
        Mixtral family swaps in a sparse-MoE expert layer). The gate
        activation is silu (Llama/Mistral/Qwen) or Gemma's
        tanh-approximate gelu per ``cfg.hidden_act``."""
        cfg = self.config
        if cfg.hidden_act == "silu":  # validated at config construction
            act = nn.silu
        else:  # "gelu": Gemma's tanh-approximate gate
            act = lambda a: nn.gelu(a, approximate=True)  # noqa: E731
        gate = dense(cfg.intermediate_size, "gate")(h)
        up = dense(cfg.intermediate_size, "up")(h)
        return dense(cfg.hidden_size, "down")(act(gate) * up)


class LlamaForCausalLM(nn.Module):
    """Returns [B, S, vocab] logits. Untied LM head (Llama-3 layout)."""

    config: LlamaConfig
    # subclasses (models/mixtral.py) swap the block while inheriting the
    # embed/RoPE/scan/decode/LM-head machinery unchanged
    block_cls = LlamaBlock

    @nn.compact
    def __call__(
        self,
        input_ids,
        positions: Optional[jnp.ndarray] = None,
        *,
        segment_ids: Optional[jnp.ndarray] = None,
        kv_mask: Optional[jnp.ndarray] = None,
        write_pos: Optional[jnp.ndarray] = None,
        train: bool = False,
        decode: bool = False,
        cache_len: Optional[int] = None,
        return_hidden: bool = False,
    ):
        cfg = self.config
        policy = current_policy()
        B, S = input_ids.shape
        if cache_len is not None and cache_len > cfg.max_seq_len:
            raise ValueError(
                f"cache_len {cache_len} > max_seq_len {cfg.max_seq_len}"
            )
        validate_write_pos(write_pos, decode, positions)
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, param_dtype=policy.param_dtype,
            dtype=policy.compute_dtype, name="embed",
        )
        x = embed(input_ids)  # dtype= already yields compute_dtype
        if cfg.scale_embedding:  # Gemma: sqrt(hidden) after lookup
            x = x * jnp.asarray(
                cfg.hidden_size ** 0.5, policy.compute_dtype
            )
        # size the tables to what this program can actually index — at
        # 128k max_seq_len (llama3_1_8b) the full table is ~67 MB of
        # constants that an S=8k step would bake in for nothing
        if decode:
            table_len = cache_len or cfg.max_seq_len
        elif positions is None:
            table_len = S
        else:
            # explicit positions (sequence-parallel shards, packed
            # batches) may index anywhere in the configured window
            table_len = cfg.max_seq_len
        cos, sin = rope_frequencies(
            cfg.head_dim, table_len, cfg.rope_theta,
            scaling=cfg.rope_scaling,
        )
        if decode:
            from pytorch_distributed_tpu.ops.attention import decode_positions

            # rotary positions continue from the decode offset; the
            # counter advances EVEN with explicit positions, so a
            # padded-prefill caller's later positions=None steps stay in
            # sync with the KV cache_index
            auto = jnp.broadcast_to(
                decode_positions(self, S)[None, :], (B, S)
            )
            if positions is None:
                positions = auto
        if segment_ids is not None and decode:
            raise ValueError(
                "segment_ids (packed training) and decode (KV cache) are "
                "mutually exclusive"
            )
        if kv_mask is not None and not decode:
            raise ValueError(
                "kv_mask is for KV-cache decode (left-padded prompts); "
                "training masks go through the loss/segment machinery"
            )
        block_cls = type(self).block_cls
        if cfg.scan_layers:
            from pytorch_distributed_tpu.models.scan import scan_stack

            x = scan_stack(
                block_cls, cfg, static_argnums=(7, 8, 9), name="layers"
            )(x, cos, sin, positions, segment_ids, kv_mask, write_pos,
              not train, decode, cache_len)
        else:
            for i in range(cfg.num_layers):
                x = block_cls(cfg, name=f"layer{i}")(
                    x, cos, sin, positions, segment_ids, kv_mask,
                    write_pos, deterministic=not train,
                    decode=decode, cache_len=cache_len,
                )
        x = RMSNorm(cfg.rms_eps, cfg.rms_offset, name="final_norm")(x)
        if return_hidden:
            # [B, S, D] for the chunked-vocab loss (ops/lm_loss.py); the
            # projection is params['lm_head']['kernel'] ([D, V]) untied,
            # or params['embed']['embedding'] ([V, D]) tied — the loss's
            # _lm_projection_weight resolves both
            return x.astype(policy.output_dtype)
        if cfg.tie_word_embeddings:
            logits = embed.attend(x)  # x is already compute_dtype
        else:
            logits = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=policy.compute_dtype,
                param_dtype=policy.param_dtype, name="lm_head",
            )(x)
        return policy.to_output(logits)


def llama_partition_rules(num_kv_heads: Optional[int] = None):
    """Megatron TP: column-parallel q/k/v/gate/up, row-parallel o/down;
    embedding sharded on hidden, lm_head kernel on vocab (its dim 1).

    A thin declarative table over the shape-aware rule engine
    (autoplan/rules.py), which supplies the behavior this function used
    to hand-roll: any dim that does not divide its mesh axes replicates
    with a once-per-shape warning — decided from the KERNEL'S OWN SHAPE
    at placement time, so MQA (Gemma-2B's 1 kv head) and ragged GQA
    (Qwen2-7B's 4 kv heads on tp=8) both replicate k/v (the smallest
    projections; q/o and the MLP still shard) instead of crashing on an
    unshardable axis, and the scan-stacked leading layer dim is
    tolerated everywhere.

    ``num_kv_heads`` is retained for back-compat: an explicit ``1``
    forces the MQA replicate form without consulting shapes; other
    values defer to the shape-based decision."""
    from pytorch_distributed_tpu.autoplan.rules import (
        TensorRule,
        engine_rules,
        replicated_rule,
    )

    kv_note = "q/o and the MLP still shard"
    kv = (
        replicated_rule(r"/(k|v)/kernel", 3)
        if num_kv_heads == 1  # forced MQA form, shapes not consulted
        else TensorRule(r"/(k|v)/kernel", (None, "tp", None), note=kv_note)
    )
    return engine_rules([
        TensorRule(r"/q/kernel", (None, "tp", None)),
        kv,
        TensorRule(r"/o/kernel", ("tp", None, None)),
        TensorRule(r"/(gate|up)/kernel", (None, "tp")),
        TensorRule(r"/down/kernel", ("tp", None)),
        TensorRule(r"embed/embedding", (None, "tp"), stacked=False),
        TensorRule(r"lm_head/kernel", (None, "tp"), stacked=False),
    ])
