"""Autoregressive generation — KV-cache decode, TPU-first.

The reference is a training-recipe repo; inference is table stakes for a
complete framework, and on TPU it has one idiomatic shape:

* **Static everything.** The KV cache is a fixed [B, max_len, H, D] buffer
  per layer (``ops.attention.decode_cache``), written with
  ``dynamic_update_slice``; the token loop is a ``lax.scan`` of a
  fixed-shape single-token step. One compile serves the whole generation,
  regardless of prompt length or tokens produced.
* **Prefill + decode.** The prompt runs through the model ONCE at full
  width (MXU-efficient), filling the cache; then the scan emits one token
  per tick. This is the standard split CUDA inference engines arrive at —
  XLA gets it from tracing two calls of the same model.
* Works with any model that takes ``decode=True`` and maintains flax
  ``cache`` collection state (GPT2LMHead, LlamaForCausalLM).

Sampling: greedy (``temperature=0``), temperature, top-k, and top-p
(nucleus) — enough to smoke-test every recipe's model family offline.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _validate_filters(top_k, top_p) -> None:
    """One home for the sampler-filter argument checks, shared by
    sample_logits (which must also raise on the greedy early-return
    path) and filter_logits (so direct consumers like speculative
    decoding are guarded without routing through sample_logits)."""
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")


def filter_logits(
    logits: jnp.ndarray,
    *,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> jnp.ndarray:
    """Temperature-scaled, k/p-filtered f32 logits ([..., vocab]).

    The exact distribution ``sample_logits`` draws from, exposed so
    rejection-sampling consumers (speculative decoding) can compute the
    same probabilities the sampler uses. ``temperature`` must be > 0
    (greedy has no distribution to filter).
    """
    if temperature <= 0.0:
        raise ValueError(
            f"filter_logits needs temperature > 0, got {temperature}"
        )
    _validate_filters(top_k, top_p)
    if top_k is not None:
        # HF clamps k to the vocab size; without this, k >= vocab fails
        # with an opaque out-of-bounds index at trace time
        top_k = min(top_k, logits.shape[-1])
    neg_inf = jnp.finfo(jnp.float32).min
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None or top_p is not None:
        # one descending sort serves both filters
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    if top_k is not None:
        kth = sorted_desc[..., top_k - 1][..., None]
        logits = jnp.where(logits < kth, neg_inf, logits)
        sorted_desc = jnp.where(
            jnp.arange(sorted_desc.shape[-1]) < top_k, sorted_desc, neg_inf
        )
    if top_p is not None:
        # a token survives if the cumulative probability BEFORE it is
        # still < top_p (so the top token always survives)
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum_before = jnp.cumsum(probs, axis=-1) - probs
        keep = cum_before < top_p
        # threshold = smallest surviving logit per row
        thresh = jnp.min(
            jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < thresh, neg_inf, logits)
    return logits


def sample_logits(
    logits: jnp.ndarray,
    rng: Optional[jax.Array],
    *,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> jnp.ndarray:
    """[B, vocab] logits -> [B] token ids.

    ``top_k`` and ``top_p`` (nucleus) filters compose like the HF
    sampler: k-filter first, then keep the smallest prefix of the
    probability-sorted vocab whose mass reaches ``top_p``.
    """
    # validate before the greedy early-return so a bad config is loud
    # even while smoke-testing with temperature=0
    _validate_filters(top_k, top_p)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if rng is None:
        raise ValueError("sampling with temperature > 0 needs an rng key")
    logits = filter_logits(
        logits, temperature=temperature, top_k=top_k, top_p=top_p
    )
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def model_max_len(model):
    """The model's position/cache capacity, or None when untyped —
    one extraction point shared by generate/generate_beam/
    generate_speculative so a new model family's limit attribute only
    needs teaching here."""
    cfg = getattr(model, "config", None)
    return getattr(cfg, "n_positions", None) or getattr(
        cfg, "max_seq_len", None
    )


def ragged_prompt_state(prompt_mask, B: int, P: int, cache_len: int):
    """Validated per-row state for a LEFT-padded (HF-style) prompt batch.

    Returns ``(prompt_mask, positions, prompt_lens, kv_mask)`` — the one
    construction of the ragged-prompt contract, shared by ``generate``
    and ``generate_speculative`` so the two can never diverge. Eager
    (non-traced) masks are refused upfront when RIGHT-padded or when a
    row has no real token at all: both would silently sample from a
    pad-slot query attending to nothing (NaN softmax / garbage tokens).
    """
    if prompt_mask.shape != (B, P):
        raise ValueError(
            f"prompt_mask must be {(B, P)}, got {prompt_mask.shape}"
        )
    prompt_mask = prompt_mask.astype(jnp.bool_)
    if not isinstance(prompt_mask, jax.core.Tracer):
        m = np.asarray(prompt_mask).astype(np.int8)
        if not (np.diff(m, axis=1) >= 0).all():
            raise ValueError(
                "prompt_mask must be LEFT-padded: each row one "
                "contiguous run of real tokens ending at the last "
                "slot (HF left-padding for decoder-only generation)"
            )
        if not m[:, -1].all():
            # left-padded + nonempty <=> last slot real; an all-pad row
            # would clamp to prompt_lens=1 and decode from a fully
            # masked attention row
            raise ValueError(
                "prompt_mask has a row with no real tokens — every row "
                "must contain at least one real (last-slot) token"
            )
    # positions count real tokens only: pads share position 0 (their
    # K/V are masked out of attention, so their rope/wpe is inert)
    positions = jnp.maximum(
        jnp.cumsum(prompt_mask.astype(jnp.int32), axis=1) - 1, 0
    )
    prompt_lens = positions[:, -1] + 1  # real tokens per row
    # cache-slot validity for the WHOLE generation: prompt slots follow
    # the mask; future decode slots are valid (the causal q_offset
    # masking hides the not-yet-written tail)
    kv_mask = jnp.concatenate(
        [prompt_mask, jnp.ones((B, cache_len - P), jnp.bool_)], axis=1
    )
    return prompt_mask, positions, prompt_lens, kv_mask


def cache_batch_axis(path, leaf) -> Optional[int]:
    """Batch axis of a decode-cache leaf, or None for shared counters.

    KV payload buffers are ``[..., B, T, H, D]`` (a leading ``[L]`` when
    layers are scanned), so the batch axis is ``ndim - 4``; the int8
    cache's per-token scale buffers carry the SAME layout and must move
    in lockstep with their payloads, and a latent cache's one leaf
    (``[..., B, T, 1, F]``) is of the same form. Index/position counters
    have no batch dim (rank 0, or ``[L]``) and return None: a leaf is
    told by its geometry, never by its name, so a block that caches
    something else declares it by caching it. Shared by ``generate_beam``
    (beam replicate/reorder) and the serving engine's slot pool
    (per-slot insert/extract) so the two can never disagree about which
    leaves are per-sequence state.
    """
    del path
    return leaf.ndim - 4 if leaf.ndim >= 4 else None


def decode_step_body(
    model,
    params,
    cache,
    tok: jnp.ndarray,
    *,
    cache_len: int,
    positions: Optional[jnp.ndarray] = None,
    kv_mask: Optional[jnp.ndarray] = None,
    write_pos: Optional[jnp.ndarray] = None,
    with_intermediates: bool = False,
):
    """One KV-cache decode tick: ``[B]`` tokens -> ``([B, V] logits, cache)``.

    The single implementation of the per-token decode body, shared by
    the offline batch path (``generate``'s scan step, ``generate_beam``)
    and the serving engine's continuous-batching tick
    (``serve/engine.py``) — the two must stay one code path so engine
    output can be pinned bit-identical to offline ``generate``.
    ``write_pos`` is the slot-pool contract (per-row KV writes at each
    row's own length, ``ops.attention.decode_cache``); the lockstep
    paths leave it None and let the model's scalar cache_index advance.
    ``with_intermediates`` also returns what the blocks sowed (an expert
    layer's routing counters), as a third element.
    """
    extra = {}
    if positions is not None:
        extra["positions"] = positions
    if kv_mask is not None:
        extra["kv_mask"] = kv_mask
    if write_pos is not None:
        extra["write_pos"] = write_pos
    logits, state = model.apply(
        {"params": params, "cache": cache},
        tok[:, None],
        decode=True,
        cache_len=cache_len,
        mutable=["cache", "intermediates"] if with_intermediates
        else ["cache"],
        **extra,
    )
    if with_intermediates:
        return logits[:, -1], state["cache"], state.get("intermediates", {})
    return logits[:, -1], state["cache"]


def _generation_limits(model, P, max_new_tokens):
    """Shared validation for generate/generate_beam: positive token count
    and prompt+new within the model's position/cache capacity. Returns
    the cache length."""
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    limit = model_max_len(model)
    if limit is not None and P + max_new_tokens > limit:
        # past the cache/position table the dynamic_update_slice clamps
        # and gathers clamp — silent garbage, so refuse up front
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) exceeds the "
            f"model's maximum sequence length {limit}"
        )
    return P + max_new_tokens


def generate(
    model,
    params,
    prompt_ids: jnp.ndarray,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    rng: Optional[jax.Array] = None,
    eos_id: Optional[int] = None,
    pad_id: int = 0,
    prompt_mask: Optional[jnp.ndarray] = None,
    repetition_penalty: float = 1.0,
    no_repeat_ngram_size: int = 0,
) -> jnp.ndarray:
    """Generate ``max_new_tokens`` continuations of ``prompt_ids`` [B, P].

    Returns [B, P + max_new_tokens]; sequences that hit ``eos_id`` are
    padded with ``pad_id`` after it. Jit-compatible end to end — wrap in
    ``jax.jit(..., static_argnums=...)`` or call inside a jitted fn; the
    decode loop is a single ``lax.scan`` either way.

    ``no_repeat_ngram_size`` matches HF's ``NoRepeatNGramLogitsProcessor``
    token-for-token for unpadded prompts (n=1 bans every seen token;
    n larger than the sequence is a no-op, like HF). Static shapes: the
    token history lives in a fixed [B, P + max_new_tokens] buffer and
    each step scans its sliding n-gram windows. With ``prompt_mask``,
    PAD slots are excluded from grams (HF scans raw input_ids, pads
    included) — the same deliberate divergence as repetition_penalty,
    keeping ragged batches equal to unpadded per-prompt runs.

    ``repetition_penalty`` (> 1.0 discourages) matches HF's
    ``RepetitionPenaltyLogitsProcessor``: logits of every token already in
    the row (prompt + generated so far) are divided by the penalty when
    positive and multiplied when negative, before sampling. One deliberate
    divergence: with ``prompt_mask``, PAD slots are not counted as seen —
    HF penalizes them because they sit in input_ids; padding is not
    content, and this keeps ragged-batch outputs equal to the unpadded
    per-prompt runs.

    ``prompt_mask`` [B, P] (True = real token) enables RAGGED batches via
    LEFT padding — the HF ``generate(attention_mask=...)`` idiom: pads
    occupy the leading slots, every row's last real token sits at slot
    P-1, positions count real tokens only, and cache slots holding pads
    are masked out of every attention step. Continuations match the
    unpadded per-prompt results.
    """
    B, P = prompt_ids.shape
    # the cache is sized to exactly what this generation needs — NOT the
    # model's max positions (at 8B scale that difference is gigabytes of
    # HBM and a proportionally wider attention every step)
    cache_len = _generation_limits(model, P, max_new_tokens)
    if rng is None:
        rng = jax.random.key(0)

    extra = {}
    prompt_lens = None
    if prompt_mask is not None:
        prompt_mask, positions, prompt_lens, kv_mask = ragged_prompt_state(
            prompt_mask, B, P, cache_len
        )
        extra = {"positions": positions, "kv_mask": kv_mask}

    if repetition_penalty <= 0.0:
        raise ValueError(
            f"repetition_penalty must be > 0, got {repetition_penalty}"
        )
    if no_repeat_ngram_size < 0:
        raise ValueError(
            f"no_repeat_ngram_size must be >= 0, got {no_repeat_ngram_size}"
        )

    # prefill: one full-width pass fills every layer's cache
    logits, state = model.apply(
        {"params": params}, prompt_ids, decode=True, cache_len=cache_len,
        mutable=["cache"], **extra,
    )
    cache = state["cache"]

    presence = None
    if repetition_penalty != 1.0:
        # [B, V] token-presence mask (prompt tokens; pads excluded when a
        # prompt_mask is given), updated as tokens are emitted
        V = logits.shape[-1]
        presence = jnp.zeros((B, V), jnp.bool_)
        rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, P))
        if prompt_mask is not None:
            # masked slots contribute a False update — a no-op under .max
            safe_ids = jnp.where(prompt_mask, prompt_ids, 0)
            presence = presence.at[rows, safe_ids].max(prompt_mask)
        else:
            presence = presence.at[rows, prompt_ids].set(True)

    def _penalize(logits, presence):
        if presence is None:
            return logits
        l32 = logits.astype(jnp.float32)
        pen = jnp.where(
            l32 > 0, l32 / repetition_penalty, l32 * repetition_penalty
        )
        return jnp.where(presence, pen, l32)

    n = no_repeat_ngram_size
    if n > cache_len:
        n = 0  # no n-gram can ever complete — a no-op, like HF
    history = None
    if n > 0:
        # fixed-size token history; slots >= cur_len are not yet written
        history = jnp.zeros((B, cache_len), jnp.int32)
        history = history.at[:, :P].set(prompt_ids.astype(jnp.int32))
        # slot validity: with a prompt_mask, PAD slots never participate
        # in grams (unlike HF's raw-input_ids scan) so ragged batches
        # keep matching the unpadded per-prompt runs — the same
        # deliberate divergence repetition_penalty documents
        if prompt_mask is not None:
            hist_valid = jnp.concatenate(
                [prompt_mask,
                 jnp.ones((B, cache_len - P), jnp.bool_)], axis=1,
            )
        else:
            hist_valid = jnp.ones((B, cache_len), jnp.bool_)
        if n >= 2:
            # sliding (n-1)-gram window start indices, built once
            win = (
                jnp.arange(cache_len - n + 1)[:, None] + jnp.arange(n - 1)
            )  # [W, n-1]

    def _ban_ngrams(logits, history, cur_len):
        """-inf on tokens that would complete a seen n-gram (HF
        semantics; n=1 bans every seen token). ``cur_len`` = tokens
        written so far; candidates extend history[cur_len-(n-1):cur_len]."""
        if history is None:
            return logits
        l32 = logits.astype(jnp.float32)
        V = l32.shape[-1]
        rows_full = jnp.arange(B)[:, None]
        if n == 1:  # every already-seen (valid) token is banned
            seen = (
                jnp.arange(cache_len)[None, :] < cur_len
            ) & hist_valid
            banned = jnp.where(seen, history, V)
            return l32.at[
                jnp.broadcast_to(rows_full, banned.shape), banned
            ].set(-jnp.inf, mode="drop")
        grams = history[:, win]  # [B, W, n-1]
        suffix = lax.dynamic_slice_in_dim(
            history, cur_len - (n - 1), n - 1, axis=1
        )  # [B, n-1]
        match = jnp.all(grams == suffix[:, None, :], axis=-1)  # [B, W]
        # a window is a real, completed n-gram iff it ends before cur_len
        ends = jnp.arange(cache_len - n + 1) + n  # window's full-gram end
        match = match & (ends[None, :] <= cur_len)
        # every slot of the gram AND its follower must be a real token
        follower_idx = jnp.arange(cache_len - n + 1) + (n - 1)
        gram_valid = jnp.all(hist_valid[:, win], axis=-1) & hist_valid[
            :, follower_idx
        ]
        match = match & gram_valid
        follower = history[:, follower_idx]
        banned = jnp.where(match, follower, V)  # V = dropped by scatter
        rows = jnp.broadcast_to(rows_full, banned.shape)
        return l32.at[rows, banned].set(-jnp.inf, mode="drop")

    rng, sub = jax.random.split(rng)
    first_logits = _penalize(logits[:, -1], presence)
    if history is not None:
        first_logits = _ban_ngrams(first_logits, history, P)
    tok = sample_logits(
        first_logits, sub, temperature=temperature,
        top_k=top_k, top_p=top_p,
    )
    if presence is not None:
        presence = presence.at[jnp.arange(B), tok].set(True)
    if history is not None:
        history = history.at[:, P].set(tok)
    done = (
        tok == eos_id if eos_id is not None
        else jnp.zeros((B,), jnp.bool_)
    )

    def step(carry, t):
        cache, tok, rng, done, presence, history = carry
        dec_extra = {}
        if prompt_lens is not None:
            # per-row positions continue each row's REAL length, not the
            # padded slot index
            dec_extra["positions"] = (prompt_lens + t)[:, None]
            dec_extra["kv_mask"] = extra["kv_mask"]
        last, cache = decode_step_body(
            model, params, cache, tok, cache_len=cache_len, **dec_extra
        )
        rng, sub = jax.random.split(rng)
        step_logits = _penalize(last, presence)
        if history is not None:
            # t counts from 0; the prefill token is already written, so
            # the history holds P + t + 1 tokens at this point
            step_logits = _ban_ngrams(step_logits, history, P + t + 1)
        nxt = sample_logits(
            step_logits, sub,
            temperature=temperature, top_k=top_k, top_p=top_p,
        )
        nxt = jnp.where(done, jnp.int32(pad_id), nxt)
        if eos_id is not None:
            done = done | (nxt == eos_id)
        if presence is not None:
            presence = presence.at[jnp.arange(B), nxt].set(True)
        if history is not None:  # traced column index -> scatter form;
            # this step's token is sequence index P + t + 1 (prefill
            # already wrote index P)
            history = history.at[
                jnp.arange(B), jnp.full((B,), P + t + 1)
            ].set(nxt)
        return (cache, nxt, rng, done, presence, history), nxt

    # scan step t consumes continuation token #t+1, whose position is
    # (real length) + t
    (cache, _, _, _, _, _), rest = lax.scan(
        step, (cache, tok, rng, done, presence, history),
        jnp.arange(max_new_tokens - 1), length=max_new_tokens - 1,
    )
    out = jnp.concatenate(
        [prompt_ids, tok[:, None], rest.T.astype(prompt_ids.dtype)], axis=1
    )
    return out


def generate_beam(
    model,
    params,
    prompt_ids: jnp.ndarray,
    *,
    max_new_tokens: int,
    num_beams: int,
    eos_id: Optional[int] = None,
    pad_id: int = 0,
    length_penalty: float = 1.0,
    return_scores: bool = False,
):
    """Beam search over the same static-cache decode loop as ``generate``.

    Deterministic (no sampling): keeps the ``num_beams`` highest
    log-probability continuations per row, finishing beams at ``eos_id``
    and ranking finished beams by ``sum(logp) / len**length_penalty``
    (HF's convention). Returns the best sequence [B, P + max_new_tokens]
    (finished beams padded with ``pad_id``), or ``(sequences, scores)``
    with ``return_scores``.

    TPU shape discipline: beams are a batch dimension — the cache is
    replicated to [B*num_beams, ...] once after prefill, and every scan
    step reorders it with one gather; all shapes static, one compile.
    """
    B, P = prompt_ids.shape
    K = num_beams
    if K < 2:
        raise ValueError("num_beams must be >= 2 (use generate for greedy)")
    cache_len = _generation_limits(model, P, max_new_tokens)
    NEG = jnp.float32(-1e30)

    # prefill once at [B, P]; expand to beams afterwards
    logits, state = model.apply(
        {"params": params}, prompt_ids, decode=True, cache_len=cache_len,
        mutable=["cache"],
    )
    logp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))  # [B, V]
    V = logp0.shape[-1]
    scores, tok = lax.top_k(logp0, K)  # [B, K] initial beams
    # replicate every layer's cache K times along its BATCH axis
    # (``cache_batch_axis``: KV payloads AND their int8 scale buffers
    # move together; counters stay shared)
    def _rep(path, x):
        ax = cache_batch_axis(path, x)
        return x if ax is None else jnp.repeat(x, K, axis=ax)

    cache = jax.tree_util.tree_map_with_path(_rep, state["cache"])
    tokens = jnp.full((B, K, max_new_tokens), pad_id, jnp.int32)
    tokens = tokens.at[:, :, 0].set(tok)
    finished = (
        tok == eos_id if eos_id is not None
        else jnp.zeros((B, K), jnp.bool_)
    )

    def step(carry, t):
        cache, tokens, scores, finished, prev = carry
        last, cache = decode_step_body(
            model, params, cache, prev.reshape(B * K),
            cache_len=cache_len,
        )
        logp = jax.nn.log_softmax(
            last.astype(jnp.float32)
        ).reshape(B, K, V)
        # finished beams may only extend with pad, at unchanged score
        pad_only = jnp.full((V,), NEG).at[pad_id].set(0.0)
        logp = jnp.where(finished[:, :, None], pad_only[None, None, :], logp)
        total = scores[:, :, None] + logp  # [B, K, V]
        flat = total.reshape(B, K * V)
        scores, idx = lax.top_k(flat, K)  # [B, K]
        beam_idx = idx // V  # which parent beam
        tok = (idx % V).astype(jnp.int32)
        # reorder histories and caches to the surviving parents
        tokens = jnp.take_along_axis(
            tokens, beam_idx[:, :, None], axis=1
        )
        tokens = tokens.at[:, :, t].set(tok)
        finished = jnp.take_along_axis(finished, beam_idx, axis=1)
        if eos_id is not None:
            finished = finished | (tok == eos_id)
        gather = (
            jnp.arange(B)[:, None] * K + beam_idx
        ).reshape(B * K)  # global cache rows

        def _take(path, x):
            ax = cache_batch_axis(path, x)
            return x if ax is None else jnp.take(x, gather, axis=ax)

        cache = jax.tree_util.tree_map_with_path(_take, cache)
        return (cache, tokens, scores, finished, tok), None

    (cache, tokens, scores, finished, _), _ = lax.scan(
        step,
        (cache, tokens, scores, finished, tok),
        jnp.arange(1, max_new_tokens),
        length=max_new_tokens - 1,
    )

    # rank by length-penalized score: finished beams use tokens-to-eos,
    # unfinished use the full length
    if eos_id is not None:
        is_eos = tokens == eos_id
        eos_pos = jnp.argmax(is_eos, axis=-1)  # first eos (0 if none)
        has_eos = jnp.any(is_eos, axis=-1)
        lengths = jnp.where(has_eos, eos_pos + 1, max_new_tokens)
    else:
        lengths = jnp.full((B, K), max_new_tokens)
    final = scores / (lengths.astype(jnp.float32) ** length_penalty)
    best = jnp.argmax(final, axis=1)  # [B]
    seq = jnp.take_along_axis(
        tokens, best[:, None, None], axis=1
    )[:, 0]  # [B, max_new_tokens]
    out = jnp.concatenate(
        [prompt_ids, seq.astype(prompt_ids.dtype)], axis=1
    )
    if return_scores:
        return out, jnp.take_along_axis(final, best[:, None], axis=1)[:, 0]
    return out
