"""Operations and bytes a latent-attention, sparse-expert decoder needs
(the ``deepseek_v3`` family), from shapes and the program's routing
counters. Beside ``flops.py``, whose family table it does not touch.

One multiply-add counts as two operations; only matrix products count.
Latent attention is counted at the CHEAPER form of each phase: a prompt
token's latent is decoded to keys and values once and attended at the
decoded head sizes; a decode token runs absorbed (its query goes up to
the latent's width, every head reads the one latent). What the program
recomputes (a chunk decodes the row's cached latents again) is not
counted, so such a program reads lower. Routed experts are counted from
the token-expert pairs the program's ``expert_pairs`` counter saw, never
from an assumed balance. The head is counted where a token is sampled:
once a prompt, once an output token."""


def dims(cfg):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "D": D, "H": H, "rq": cfg["q_lora_rank"], "r": cfg["kv_lora_rank"],
        "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "F_dense": cfg["intermediate_size"],
        "F": cfg["moe_intermediate_size"],
        "Fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        "E": cfg["router_experts"], "held": cfg["n_routed_experts"],
        "V": cfg["vocab_size"],
        "dense_layers": cfg["first_k_dense_replace"],
        "expert_layers": (cfg["num_hidden_layers"]
                          - cfg["first_k_dense_replace"]),
        "layers": cfg["num_hidden_layers"],
    }


def attention_params(cfg):
    """(projections every token multiplies, the up-projection W_ukv)."""
    d = dims(cfg)
    proj = (d["D"] * d["rq"] + d["rq"] * d["H"] * (d["dn"] + d["dr"])
            + d["D"] * (d["r"] + d["dr"]) + d["H"] * d["dv"] * d["D"])
    return proj, d["r"] * d["H"] * (d["dn"] + d["dv"])


def expert_params(cfg):
    """One routed expert's three matrices."""
    d = dims(cfg)
    return 3 * d["D"] * d["F"]


def param_count(cfg):
    """Every parameter the configuration file holds on this chip."""
    d = dims(cfg)
    proj, up = attention_params(cfg)
    attn = proj + up + 2 * d["D"] + d["rq"] + d["r"]   # and four norms
    dense = attn + 3 * d["D"] * d["F_dense"]
    expert = (attn + d["D"] * d["E"] + d["E"] + d["held"] * expert_params(cfg)
              + 3 * d["D"] * d["Fs"])
    return (d["dense_layers"] * dense + d["expert_layers"] * expert
            + 2 * d["V"] * d["D"] + d["D"])


def latent_bytes_per_token(cfg, itemsize=2):
    """What the mathematics caches a token: latent and rotary key, every
    layer (the pool stores them in whole 128-lane tiles, more)."""
    d = dims(cfg)
    return d["layers"] * (d["r"] + d["dr"]) * itemsize


def dense_flops_per_token(cfg, *, absorbed):
    """Operations of one token in every matrix it multiplies whatever
    the router says: attention projections (with W_ukv in the phase's
    form), the dense layers' SwiGLU, router and shared expert."""
    d = dims(cfg)
    proj, up = attention_params(cfg)
    if absorbed:  # q_n up to the latent's width, the output back down
        up = d["H"] * d["r"] * (d["dn"] + d["dv"])
    per_layer = proj + up
    ffn = (d["dense_layers"] * 3 * d["D"] * d["F_dense"]
           + d["expert_layers"] * (d["D"] * d["E"] + 3 * d["D"] * d["Fs"]))
    return 2 * (d["layers"] * per_layer + ffn)


def head_flops(cfg):
    d = dims(cfg)
    return 2 * d["D"] * d["V"]


def routed_flops(cfg, pairs):
    """Operations of ``pairs`` token-expert pairs through their experts."""
    return 2 * expert_params(cfg) * pairs


def attention_flops_prefill(cfg, start, stop):
    """Decoded form: positions ``[start, stop)``, each attending itself
    and every earlier position at head sizes ``dn + dr`` and ``dv``."""
    d = dims(cfg)
    keys = (stop * (stop + 1) - start * (start + 1)) // 2
    return 2 * d["layers"] * d["H"] * (d["dn"] + d["dr"] + d["dv"]) * keys


def attention_flops_decode(cfg, keys):
    """Absorbed form: one query a head against ``keys`` latents, scores
    over ``r + dr`` and values over ``r``, in every layer."""
    d = dims(cfg)
    return 2 * d["layers"] * d["H"] * (2 * d["r"] + d["dr"]) * keys


def prompt_flops(cfg, length):
    """A prompt of ``length`` tokens, without its routed experts."""
    return (length * dense_flops_per_token(cfg, absorbed=False)
            + attention_flops_prefill(cfg, 0, length) + head_flops(cfg))


def decode_token_flops(cfg, keys):
    """One output token attending ``keys`` positions (itself included),
    without its routed experts."""
    return (dense_flops_per_token(cfg, absorbed=True)
            + attention_flops_decode(cfg, keys) + head_flops(cfg))


def latent_attention_call(cfg, lengths, itemsize=2):
    """(operations, bytes) ONE layer's absorbed decode call needs: each
    row's one query a head against ``lengths[i]`` cached latents, each
    latent and rotary key read once, the queries read and the outputs
    written."""
    d = dims(cfg)
    keys = sum(lengths)
    flops = 2 * d["H"] * (2 * d["r"] + d["dr"]) * keys
    rows = len(lengths)
    nbytes = itemsize * (
        (d["r"] + d["dr"]) * keys
        + rows * d["H"] * (d["r"] + d["dr"]) + rows * d["H"] * d["r"]
    )
    return flops, nbytes


def expert_gmm_call(cfg, pairs, hit, itemsize=2):
    """(operations, bytes) ONE expert layer's grouped products need for
    ``pairs`` token-expert pairs over ``hit`` experts: the three
    matrices of every expert hit read once, each pair's row read for
    gate and up, the hidden row written and read, the output written."""
    d = dims(cfg)
    flops = 2 * expert_params(cfg) * pairs
    nbytes = itemsize * (
        hit * expert_params(cfg)
        + pairs * (2 * d["D"] + 4 * d["F"] + d["D"])
    )
    return flops, nbytes
