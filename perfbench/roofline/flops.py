"""Operations and bytes the algorithm needs, from shapes alone.

One multiply-add counts as two operations. Only matrix products count
(norms, softmax, activations and the optimizer are left out, as is usual
for MFU). Recomputation under remat is NOT counted: the numbers are what
the mathematics requires, so a program that recomputes more reads lower.
Each family gives ``matmul_params(cfg)`` (the weights every token
multiplies, the head included, embeddings' lookups excluded) and
``attn_dims(cfg)`` = (layers, query heads, head size, kv heads, window).
"""


def gpt2_dims(cfg):
    D, L, V = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    per_layer = D * 3 * D + D * D + 2 * D * 4 * D
    return {
        "matmul_params": L * per_layer + V * D,  # tied head counts once
        "layers": L, "heads": cfg["n_head"], "head_dim": D // cfg["n_head"],
        "kv_heads": cfg["n_head"], "window": None,
    }


def mistral_dims(cfg):
    D, F, L, V = (
        cfg["hidden_size"], cfg["intermediate_size"],
        cfg["num_hidden_layers"], cfg["vocab_size"],
    )
    H, K, hd = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"],
    )
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    return {
        "matmul_params": L * per_layer + D * V,
        "layers": L, "heads": H, "head_dim": hd, "kv_heads": K,
        "window": cfg["sliding_window"],
    }


def dims(cfg):
    return {"gpt2": gpt2_dims, "mistral": mistral_dims}[cfg["family"]](cfg)


def param_count(cfg):
    """Every parameter of the model as the configuration file cuts it."""
    if cfg["family"] == "gpt2":
        D, L, V, P = (
            cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"],
            cfg["n_positions"],
        )
        per_layer = (
            4 * D + D * 3 * D + 3 * D + D * D + D + D * 4 * D + 4 * D
            + 4 * D * D + D
        )
        return L * per_layer + V * D + P * D + 2 * D
    d = mistral_dims(cfg)
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    # matmul_params holds the head; add the embedding and the norms
    return d["matmul_params"] + V * D + d["layers"] * 2 * D + D


def kv_bytes_per_token(cfg, itemsize=2):
    d = dims(cfg)
    return 2 * d["layers"] * d["kv_heads"] * d["head_dim"] * itemsize


def _attended(pos, window):
    """Keys position ``pos`` (0-based) attends: itself and those before
    it, at most ``window``."""
    n = pos + 1
    return n if window is None else min(n, window)


def attn_flops_span(cfg, start, stop):
    """Forward attention operations (QK^T and PV) of the tokens at
    positions ``[start, stop)`` of one sequence, each attending its
    causal (windowed) context."""
    d = dims(cfg)
    w = d["window"]
    if w is None or stop <= w:
        keys = (stop * (stop + 1) - start * (start + 1)) // 2
    else:
        keys = sum(_attended(p, w) for p in range(start, stop))
    return 4 * d["layers"] * d["heads"] * d["head_dim"] * keys


def forward_flops_span(cfg, start, stop):
    """Forward operations of the tokens at positions ``[start, stop)``."""
    return 2 * dims(cfg)["matmul_params"] * (stop - start) + attn_flops_span(
        cfg, start, stop
    )


def train_step_flops(cfg, batch, seq_len):
    """Forward plus backward (twice the forward) of one optimizer step;
    the forward recomputed under remat is not counted."""
    return 3 * batch * forward_flops_span(cfg, 0, seq_len)


def paged_attention_call(cfg, lengths, itemsize=2):
    """(operations, bytes) one decode call of the paged-attention kernel
    needs for ONE layer: each row's one query against ``lengths[i]``
    cached keys and values (window-capped), reading each K and V entry
    once and the queries, writing the outputs."""
    d = dims(cfg)
    H, K, hd, w = d["heads"], d["kv_heads"], d["head_dim"], d["window"]
    keys = sum(n if w is None else min(n, w) for n in lengths)
    flops = 4 * H * hd * keys
    nbytes = 2 * K * hd * itemsize * keys + 2 * len(lengths) * H * hd * itemsize
    return flops, nbytes
