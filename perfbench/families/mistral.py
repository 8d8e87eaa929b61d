"""Mistral family: configuration file -> ``models/mistral.py`` (the
Llama body with a sliding window)."""

import jax.numpy as jnp

STACK = ("layers", "block")


def model_config(cfg, *, remat=False):
    from pytorch_distributed_tpu.models.mistral import MistralConfig

    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise ValueError("head_dim must be hidden_size / heads here")
    return MistralConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
        sliding_window=cfg["sliding_window"],
        tie_word_embeddings=cfg["tie_word_embeddings"], remat=remat,
    )


def build_model(cfg, **kw):
    from pytorch_distributed_tpu.models.mistral import MistralForCausalLM

    return MistralForCausalLM(model_config(cfg, **kw))


def partition_rules():
    from pytorch_distributed_tpu.models.mistral import (
        mistral_partition_rules,
    )

    return mistral_partition_rules()


def num_layers(cfg):
    return cfg["num_hidden_layers"]


def max_positions(cfg):
    return cfg["max_position_embeddings"]


def top_spec(cfg):
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "embed/embedding": ((V, D), "normal"),
        "final_norm/scale": ((D,), "scale"),
        "lm_head/kernel": ((D, V), "normal"),
    }


def layer_spec(cfg):
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, K, hd = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"],
    )
    return {
        "attn_norm/scale": ((D,), "scale"),
        "q/kernel": ((D, H, hd), "normal"),
        "k/kernel": ((D, K, hd), "normal"),
        "v/kernel": ((D, K, hd), "normal"),
        "o/kernel": ((H, hd, D), "normal"),
        "mlp_norm/scale": ((D,), "scale"),
        "gate/kernel": ((D, F), "normal"),
        "up/kernel": ((D, F), "normal"),
        "down/kernel": ((F, D), "normal"),
    }


def param_dtype(cfg):
    return jnp.dtype(cfg["precision"]["param_dtype"])
