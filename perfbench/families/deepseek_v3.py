"""DeepSeek-V3 family (GigaChat3.1): configuration file ->
``models/deepseek_v3.py``, cut to the experts one chip of an
expert-parallel group holds.

The configuration's ``n_routed_experts`` counts the experts HELD here
(``reduced``); the router keeps the published width, ``router_experts``,
and the chip is rank ``deployment_rank`` of the ``router_experts /
n_routed_experts`` that share a layer. The leading dense layer is
unrolled ahead of the scanned expert stack, so its leaves are top
leaves under ``dense0/``; ``STACK`` holds the expert layers alone, and
their routed experts' tensors are the top leaves ``experts/w_*``,
``[L, held, ..]`` (the model hands them to the layer loop whole)."""

import jax.numpy as jnp

STACK = ("layers", "block")


def held(cfg):
    """(first, count) of the routed experts this chip holds."""
    n = cfg["n_routed_experts"]
    return cfg.get("deployment_rank", 0) * n, n


def model_config(cfg):
    from pytorch_distributed_tpu.models.deepseek_v3 import (
        DeepseekV3Config, YarnScaling,
    )

    if cfg["first_k_dense_replace"] != 1 or cfg["moe_layer_freq"] != 1:
        raise ValueError("the family file lays out ONE leading dense layer")
    rs = cfg["rope_scaling"]
    return DeepseekV3Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["router_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        experts_held=held(cfg),
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"]),
        ),
        rms_eps=cfg["rms_norm_eps"],
    )


def build_model(cfg, **kw):
    from pytorch_distributed_tpu.models.deepseek_v3 import (
        DeepseekV3ForCausalLM,
    )

    return DeepseekV3ForCausalLM(model_config(cfg, **kw))


def partition_rules():
    from pytorch_distributed_tpu.models.deepseek_v3 import (
        deepseek_v3_partition_rules,
    )

    return deepseek_v3_partition_rules()


def num_layers(cfg):
    """Layers of the scanned stack: the expert layers."""
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def max_positions(cfg):
    return cfg["max_position_embeddings"]


def attention_spec(cfg):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return {
        "attn_norm/scale": ((D,), "scale"),
        "attn/q_a/kernel": ((D, rq), "normal"),
        "attn/q_norm/scale": ((rq,), "scale"),
        "attn/q_b/kernel": ((rq, H, dn + dr), "normal"),
        "attn/kv_a/kernel": ((D, r + dr), "normal"),
        "attn/kv_norm/scale": ((r,), "scale"),
        "attn/kv_b": ((r, H, dn + dv), "normal"),
        "attn/o/kernel": ((H, dv, D), "normal"),
        "mlp_norm/scale": ((D,), "scale"),
    }


def top_spec(cfg):
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    out = {
        "embed/embedding": ((V, D), "normal"),
        "final_norm/scale": ((D,), "scale"),
        "lm_head/kernel": ((D, V), "normal"),
        "dense0/gate/kernel": ((D, F), "normal"),
        "dense0/up/kernel": ((D, F), "normal"),
        "dense0/down/kernel": ((F, D), "normal"),
    }
    out.update({f"dense0/{k}": v for k, v in attention_spec(cfg).items()})
    # the routed experts of every expert layer: [L, held, ..] leaves the
    # layer loop broadcasts and the grouped product reads in place
    L, n, Fe = num_layers(cfg), cfg["n_routed_experts"], cfg[
        "moe_intermediate_size"]
    out.update({
        "experts/w_gate": ((L, n, D, Fe), "normal"),
        "experts/w_in": ((L, n, D, Fe), "normal"),
        "experts/w_out": ((L, n, Fe, D), "normal"),
    })
    return out


def layer_spec(cfg):
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E = cfg["router_experts"]
    Fs = F * cfg["n_shared_experts"]
    out = attention_spec(cfg)
    out.update({
        "moe/router/kernel": ((D, E), "normal"),
        "moe/router_bias": ((E,), "normal"),
        "moe/shared_gate/kernel": ((D, Fs), "normal"),
        "moe/shared_up/kernel": ((D, Fs), "normal"),
        "moe/shared_down/kernel": ((Fs, D), "normal"),
    })
    return out


def param_dtype(cfg):
    return jnp.dtype(cfg["precision"]["param_dtype"])
