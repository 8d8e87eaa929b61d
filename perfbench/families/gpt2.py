"""GPT-2 family: configuration file -> ``models/gpt2.py``."""

import jax.numpy as jnp

STACK = ("blocks", "block")  # where the scanned layers sit in the tree
# leaves that fuse several of the published description's tensors:
# path -> (axis of the one-layer leaf, the parts along it)
SPLIT = {
    "attn_qkv/kernel": (1, ("q", "k", "v")),
    "attn_qkv/bias": (0, ("q", "k", "v")),
}


def model_config(cfg, *, remat=False):
    from pytorch_distributed_tpu.models import GPT2Config

    for k in ("resid_pdrop", "embd_pdrop"):
        if cfg[k] != cfg["resid_pdrop"]:
            raise ValueError("the program has one dropout rate")
    return GPT2Config(
        vocab_size=cfg["vocab_size"], n_positions=cfg["n_positions"],
        hidden_size=cfg["n_embd"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], dropout_rate=cfg["resid_pdrop"],
        layer_norm_eps=cfg["layer_norm_epsilon"], remat=remat,
    )


def build_model(cfg, **kw):
    from pytorch_distributed_tpu.models import GPT2LMHead

    return GPT2LMHead(model_config(cfg, **kw))


def partition_rules():
    from pytorch_distributed_tpu.models import gpt2_partition_rules

    return gpt2_partition_rules()


def num_layers(cfg):
    return cfg["n_layer"]


def max_positions(cfg):
    return cfg["n_positions"]


def top_spec(cfg):
    D = cfg["n_embd"]
    return {
        "wte/embedding": ((cfg["vocab_size"], D), "normal"),
        "wpe/embedding": ((cfg["n_positions"], D), "normal"),
        "ln_f/scale": ((D,), "scale"),
        "ln_f/bias": ((D,), "normal"),
    }


def layer_spec(cfg):
    D, H = cfg["n_embd"], cfg["n_head"]
    hd = D // H
    return {
        "ln1/scale": ((D,), "scale"),
        "ln1/bias": ((D,), "normal"),
        "attn_qkv/kernel": ((D, 3, H, hd), "normal"),
        "attn_qkv/bias": ((3, H, hd), "normal"),
        "attn_out/kernel": ((H, hd, D), "normal"),
        "attn_out/bias": ((D,), "normal"),
        "ln2/scale": ((D,), "scale"),
        "ln2/bias": ((D,), "normal"),
        "mlp_up/kernel": ((D, 4 * D), "normal"),
        "mlp_up/bias": ((4 * D,), "normal"),
        "mlp_down/kernel": ((4 * D, D), "normal"),
        "mlp_down/bias": ((D,), "normal"),
    }


def param_dtype(cfg):
    return jnp.dtype(cfg["precision"]["param_dtype"])
