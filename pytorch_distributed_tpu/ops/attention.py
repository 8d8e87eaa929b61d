"""Attention + rotary embeddings, TPU-first.

Design notes:

* Grouped-query attention is computed with the KV-head group kept as an
  einsum dimension — no ``repeat`` materialization of KV to Q heads
  (saves HBM bandwidth, the usual TPU bottleneck).
* Logits/softmax accumulate in f32 while inputs stay bf16 (MXU-native);
  this is the numerically-safe AMP pattern the reference gets from CUDA
  autocast's op allowlist.
* Static shapes and a closed-form causal mask — nothing data-dependent,
  so XLA can fuse the whole thing.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def rope_frequencies(
    head_dim: int, max_seq_len: int, theta: float = 10_000.0,
    scaling=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin) tables of shape [max_seq_len, head_dim//2], f32.

    ``scaling`` (a ``models.llama.RopeScaling`` or None) extends a
    pretrained context window:

    * ``"linear"`` — position-interpolation (Chen et al. 2023):
      positions divided by ``factor``;
    * ``"llama3"`` — HF's Llama-3.1 frequency-dependent scheme:
      wavelengths longer than ``original_max_position_embeddings /
      low_freq_factor`` are slowed by ``factor``, wavelengths shorter
      than ``original / high_freq_factor`` kept, the band between
      smoothly interpolated. Matches HF ``_compute_llama3_parameters``
      so converted Llama-3.1 checkpoints score identically;
    * ``"yarn"`` — :func:`yarn_inverse_frequencies` (DeepSeek-V3).
    """
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    if scaling is not None:
        kind = scaling.type
        if kind == "linear":
            t = t / scaling.factor
        elif kind == "llama3":
            orig = scaling.original_max_position_embeddings
            lo_w = orig / scaling.low_freq_factor   # longest kept-ish
            hi_w = orig / scaling.high_freq_factor  # shortest scaled-ish
            wavelen = 2.0 * jnp.pi / inv
            smooth = (
                orig / wavelen - scaling.low_freq_factor
            ) / (scaling.high_freq_factor - scaling.low_freq_factor)
            smoothed = (
                (1.0 - smooth) * inv / scaling.factor + smooth * inv
            )
            inv = jnp.where(
                wavelen > lo_w,
                inv / scaling.factor,  # low-freq: fully slowed
                jnp.where(wavelen < hi_w, inv, smoothed),  # high: kept
            )
        elif kind == "yarn":
            inv = yarn_inverse_frequencies(head_dim, theta, scaling)
        else:
            raise NotImplementedError(
                f"rope scaling type {kind!r} (supported: linear, llama3, yarn; "
                "'dynamic' NTK rescales per sequence length — a dynamic "
                "shape under jit — use llama3 or linear instead)"
            )
    freqs = jnp.outer(t, inv)  # [S, D/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def yarn_inverse_frequencies(head_dim: int, theta: float, scaling):
    """YaRN (Peng et al. 2023) as DeepSeek-V3 applies it: a rotary pair
    that turns more than ``beta_fast`` times inside the original window
    keeps its frequency, one that turns fewer than ``beta_slow`` times
    is slowed by ``factor``, and the pairs between are blended on a
    linear ramp over the pair index. ``scaling`` carries ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``.
    (The matching softmax temperature is :func:`yarn_mscale`.)"""
    half = head_dim // 2
    extra = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    inter = extra / scaling.factor
    orig = scaling.original_max_position_embeddings

    def pair_at(turns):  # the pair that makes ``turns`` turns in ``orig``
        return (head_dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_at(scaling.beta_fast)), 0)
    high = min(math.ceil(pair_at(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0
    )
    return inter * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``
    (1 for ``factor <= 1``); DeepSeek-V3 multiplies the softmax scale by
    its square."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Rotate [B, S, H, D] by position. Tables are gathered at ``positions``
    (default arange) — pass explicit positions for sequence-parallel shards."""
    if positions is None:
        c = cos[: x.shape[1]][None, :, None, :]
        s = sin[: x.shape[1]][None, :, None, :]
    else:
        c = cos[positions][:, :, None, :]
        s = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def dot_product_attention(
    q: jnp.ndarray,  # [B, S, Hq, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,  # [B, T, Hkv, D]
    *,
    causal: bool = False,
    mask: Optional[jnp.ndarray] = None,  # [B, 1|Hq, S, T] or [B, T] padding
    segment_ids: Optional[jnp.ndarray] = None,  # [B, S] packing ids
    q_offset: int = 0,
    bias: Optional[jnp.ndarray] = None,  # [1|B, Hq, S, T] additive
    scale: Optional[float] = None,
    softmax_dtype=jnp.float32,
    dropout_rate: float = 0.0,
    dropout_rng=None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """MXU-friendly grouped attention; returns [B, S, Hq, Dv] in q.dtype
    (``v``'s head size may differ from ``q``'s and ``k``'s).

    ``q_offset`` shifts query positions for the causal mask — used by
    sequence-parallel shards where the local block starts mid-sequence.
    A ``[B]`` array gives every batch row its OWN offset (the serving
    engine's slot pool, where each slot's sequence has a different
    length); the causal mask then hides each row's unwritten cache tail
    independently.
    ``segment_ids`` restricts attention to within-segment pairs (packed
    fixed-shape sequences; self-attention only).
    ``bias`` is added to the logits before masking — T5 relative position
    buckets, ALiBi slopes. ``scale`` overrides the 1/sqrt(D) default
    (T5 folds the scale into its init and uses 1.0).
    ``dropout_rate``/``dropout_rng`` drop attention WEIGHTS (post-softmax,
    inverted scaling) — torch's ``attn_dropout`` / HF T5 semantics.
    ``window`` is sliding-window (Mistral) attention: position ``i``
    sees only keys in ``(i - window, i]`` — HF's convention, where a
    key exactly ``window`` back is already masked. Composes with the
    causal mask it implies and with KV-cache decode (traced
    ``q_offset``): the cache buffer stays full-length, the band mask
    bounds what each step reads.
    """
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    if Hq % Hkv != 0:
        raise ValueError(f"query heads {Hq} not a multiple of kv heads {Hkv}")
    G = Hq // Hkv

    qg = q.reshape(B, S, Hkv, G, D)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    # [B, Hkv, G, S, T]; accumulate in f32 on the MXU, not post-cast
    logits = (
        jnp.einsum(
            "bskgd,btkd->bkgst", qg, k, preferred_element_type=softmax_dtype
        )
        * scale
    )
    if bias is not None:
        logits = logits + bias.reshape(
            bias.shape[0], Hkv, G, *bias.shape[-2:]
        ).astype(softmax_dtype)

    neg = jnp.finfo(softmax_dtype).min
    if segment_ids is not None:
        if S != T:
            raise ValueError("segment_ids requires self-attention (S == T)")
        same = segment_ids[:, :, None] == segment_ids[:, None, :]  # [B,S,T]
        logits = jnp.where(same[:, None, None], logits, neg)
    if causal or window is not None:
        if window is not None and window <= 0:
            # an all-masked row would softmax to UNIFORM weights over
            # every key (future included) — garbage, silently
            raise ValueError(f"window must be positive, got {window}")
        if getattr(q_offset, "ndim", 0) == 1:  # per-row offsets [B]
            qpos = q_offset[:, None] + jnp.arange(S)[None, :]  # [B, S]
        else:
            qpos = jnp.arange(S) + q_offset  # [S]
        kpos = jnp.arange(T)
        keep = qpos[..., :, None] >= kpos  # [S, T] or [B, S, T]
        if window is not None:
            # band: key strictly within `window` positions back
            keep = keep & (qpos[..., :, None] - kpos < window)
        # broadcast into the [B, Hkv, G, S, T] logits layout
        keep = (
            keep[:, None, None] if keep.ndim == 3 else keep[None, None, None]
        )
        logits = jnp.where(keep, logits, neg)
    if mask is not None:
        if mask.ndim == 2:  # [B, T] key padding mask
            mask = mask[:, None, None, None, :]
        elif mask.ndim == 4:  # [B, H, S, T] -> group layout
            h = mask.shape[1]
            mask = (
                mask.reshape(B, Hkv, G, S, T)
                if h == Hq
                else mask[:, :, None, :, :]
            )
        logits = jnp.where(mask, logits, neg)

    weights = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError(
                "dropout_rate > 0 requires dropout_rng (pass the module's "
                "make_rng('dropout') stream)"
            )
        keep = jax.random.bernoulli(
            dropout_rng, 1.0 - dropout_rate, weights.shape
        )
        weights = jnp.where(keep, weights / (1.0 - dropout_rate), 0.0)
    out = jnp.einsum("bkgst,btkd->bskgd", weights.astype(q.dtype), v)
    return out.reshape(B, S, Hq, v.shape[-1])


def decode_positions(module, seq_len: int) -> jnp.ndarray:
    """Model-level decode position counter: [seq_len] absolute positions.

    Learned position tables (GPT-2) and rotary embeddings (Llama) both
    need the decode offset BEFORE the blocks run; this keeps one counter
    in the model's own ``cache`` collection, advanced per call.
    """
    pos = module.variable(
        "cache", "position", lambda: jnp.zeros((), jnp.int32)
    )
    positions = pos.value + jnp.arange(seq_len)
    pos.value = pos.value + seq_len
    return positions


def _q8_rows(x):
    """Symmetric per-(batch, position, head) int8: [..., D] -> (q8, scale).

    The scale reduces ONLY the head_dim axis, so every cached token
    keeps its own range — outlier tokens can't flatten their neighbors.
    The quantization core is shared with the weight-tree path
    (ops/quant.py) so rounding/clamp semantics cannot drift.
    """
    from pytorch_distributed_tpu.ops.quant import symmetric_int8

    return symmetric_int8(x, -1)


def validate_write_pos(write_pos, decode: bool, positions) -> None:
    """The model-level precondition of per-row KV writes, in ONE place
    (gpt2/llama/neox forwards all call it): ``write_pos`` comes with
    ``decode=True`` AND explicit per-row positions or not at all — the
    shared ``decode_positions`` counter would embed every slot at one
    drifting position while its KV lands at its own offset, silent
    garbage. Must run BEFORE the model's auto-positions fallback."""
    if write_pos is not None and (not decode or positions is None):
        raise ValueError(
            "write_pos (slot-pool decode) requires decode=True AND "
            "explicit per-row positions"
        )


def _dense_writer(module, S: int, write_pos):
    """``(cache_index variable, offset, advance, write(buf, new))`` of a
    dense ``[B, max_len, H, D]`` decode cache: lockstep (every row at
    the shared scalar ``cache_index``, which the caller advances to
    ``advance``) or, with ``write_pos [B]``, per row (``advance`` None:
    the scalar counter stays untouched)."""
    ci = module.variable(
        "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
    )
    if write_pos is not None:

        def write(buf, new):
            # row b's [S, H, D] update lands at its own buffer position
            return jax.vmap(
                lambda row, upd, pos: jax.lax.dynamic_update_slice(
                    row, upd, (pos, 0, 0)
                )
            )(buf, new.astype(buf.dtype), write_pos)

        return ci, write_pos, None, write
    offset = ci.value

    def write(buf, new):
        return jax.lax.dynamic_update_slice(
            buf, new.astype(buf.dtype), (0, offset, 0, 0)
        )

    return ci, offset, offset + S, write


def decode_cache(
    module,
    k,
    v,
    max_len: int,
    quantize: Optional[str] = None,
    write_pos=None,
):
    """Append k/v to this block's KV cache (flax ``cache`` collection).

    TPU-first decode: the cache is a STATIC [B, max_len, H, D] buffer
    written with ``dynamic_update_slice`` — no growing shapes, so one
    compiled step serves every position and `lax.scan` can drive the token
    loop. Returns ``(k_all, v_all, offset)`` where offset is the (traced)
    number of tokens already cached; attend with ``q_offset=offset`` so
    the causal mask hides both the future and the unwritten tail.

    ``write_pos`` (a ``[B]`` int32 array) switches to PER-ROW writes —
    the serving engine's slot-pool contract, where each batch row is an
    independent request whose sequence occupies buffer slots
    ``[0, write_pos[b])``: row ``b``'s ``S`` new entries land at
    ``write_pos[b] .. write_pos[b]+S-1`` (a vmapped
    ``dynamic_update_slice``), the shared scalar ``cache_index`` is
    neither consulted nor advanced (slots don't move in lockstep), and
    the returned offset is ``write_pos`` itself — feeding attention's
    per-row ``q_offset`` form so each row's causal mask ends at its own
    length. The caller owns position accounting (pass explicit
    ``positions`` at the model level).

    ``quantize="int8"`` stores the cache as int8 payloads + per-token
    f32 scales (~2x less HBM at rest vs a bf16 cache, ~4x vs f32 — the
    scales add 4/head_dim bytes/element; at long context the KV cache,
    not the weights, is the serving memory ceiling). Entries
    quantize at write; the read dequantizes into the attention einsum,
    which XLA fuses — the RESIDENT buffer stays int8, the bf16
    reconstruction is a streamed transient. Lossy (~1e-2 relative per
    entry): token agreement with the exact cache is high but not pinned
    bitwise — see tests/test_attention.py.

    Under an active :class:`ops.paged_attention.PagedView` (the serving
    engine's paged decode programs), the cache variables are the PAGE
    POOL (``[num_pages + 1, page_size, H * D]`` frames, initialized by
    ``serve.kv_slots.init_page_cache``): the write narrows to a
    per-page scatter of only the W deliberately-written positions
    (``paged_write`` — inactive rows drop theirs entirely, never a
    dense intermediate), and the returned k/v ARE the pool buffers
    (int8: a :class:`~.paged_attention.PagedKVQuant` payload+scale
    pair), which :func:`attention` streams in place. ``write_pos`` is
    mandatory there — paged decode has no lockstep cache_index form.
    """
    B, S, H, D = k.shape
    if quantize not in (None, "int8"):
        raise ValueError(
            f"quantize must be None or 'int8', got {quantize!r}"
        )
    from pytorch_distributed_tpu.ops.paged_attention import active_view

    pv = active_view()
    if pv is not None:
        return _decode_cache_paged(module, k, v, quantize, write_pos, pv)
    ci, offset, advance, _write = _dense_writer(module, S, write_pos)
    if quantize == "int8":
        ck = module.variable(
            "cache", "cached_key", jnp.zeros, (B, max_len, H, D), jnp.int8
        )
        cks = module.variable(
            "cache", "cached_key_scale", jnp.ones,
            (B, max_len, H, 1), jnp.float32,
        )
        cv = module.variable(
            "cache", "cached_value", jnp.zeros, (B, max_len, H, D),
            jnp.int8,
        )
        cvs = module.variable(
            "cache", "cached_value_scale", jnp.ones,
            (B, max_len, H, 1), jnp.float32,
        )
        qk, sk = _q8_rows(k)
        qv, sv = _q8_rows(v)
        ck.value = _write(ck.value, qk)
        cks.value = _write(cks.value, sk)
        cv.value = _write(cv.value, qv)
        cvs.value = _write(cvs.value, sv)
        if advance is not None:
            ci.value = advance
        k_all = (
            ck.value.astype(jnp.float32) * cks.value
        ).astype(k.dtype)
        v_all = (
            cv.value.astype(jnp.float32) * cvs.value
        ).astype(v.dtype)
        return k_all, v_all, offset
    ck = module.variable(
        "cache", "cached_key", jnp.zeros, (B, max_len, H, D), k.dtype
    )
    cv = module.variable(
        "cache", "cached_value", jnp.zeros, (B, max_len, H, D), v.dtype
    )
    ck.value = _write(ck.value, k)
    cv.value = _write(cv.value, v)
    if advance is not None:
        ci.value = advance
    return ck.value, cv.value, offset


def decode_latent_cache(module, latent, max_len: int, value_dim: int,
                        write_pos=None):
    """Append ``latent [B, S, 1, F]`` to a block's ONE-leaf decode cache
    (``cached_latent``): multi-head latent attention caches one frame a
    token that every head shares, whose first ``value_dim`` lanes are
    also the values. Returns ``(k_all, v_all, offset)`` for
    :func:`attention`, as :func:`decode_cache` does: the dense
    ``[B, max_len, 1, F]`` buffer and its ``[..., :value_dim]``; under
    an active ``PagedView`` the pool leaf where it lies, and a
    :class:`~.paged_attention.PagedPrefix` of it."""
    from pytorch_distributed_tpu.ops.paged_attention import (
        PagedPrefix,
        active_view,
        paged_write,
    )

    B, S, H, F = latent.shape
    pv = active_view()
    if pv is not None:
        if write_pos is None:
            raise ValueError(
                "paged decode (an active PagedView) requires write_pos"
            )
        pool = module.variable("cache", "cached_latent", None)
        _check_pool_leaf(pool.value, pv)
        pool.value = paged_write(
            pool.value, latent, pv.page_tables, write_pos, pv.keep, pv.layer
        )
        return pool.value, PagedPrefix(pool.value, value_dim), write_pos
    ci, offset, advance, write = _dense_writer(module, S, write_pos)
    buf = module.variable(
        "cache", "cached_latent", jnp.zeros, (B, max_len, H, F), latent.dtype
    )
    buf.value = write(buf.value, latent)
    if advance is not None:
        ci.value = advance
    return buf.value, buf.value[..., :value_dim], offset


def _check_pool_leaf(leaf, pv):
    """A cache variable under an active ``PagedView`` must be a page-pool
    leaf; a dense ``[B, max_len, ...]`` buffer there means a caller
    installed the view around a cache it never paged — refused loudly,
    since the per-page write arithmetic would silently corrupt it."""
    rank = 3 if pv.layer is None else 4
    if leaf is None or leaf.ndim != rank or leaf.shape[-2] != pv.page_size:
        raise ValueError(
            f"paged decode needs a page-pool cache ([num_pages + 1, "
            f"page_size={pv.page_size}, H * D] from "
            f"serve.kv_slots.init_page_cache, stacked [L, ...] exactly "
            f"when a layer scan names the layer); found "
            f"{None if leaf is None else leaf.shape} with layer "
            f"{'None' if pv.layer is None else 'given'}"
        )


def _decode_cache_paged(module, k, v, quantize, write_pos, pv):
    """The paged-pool form of ``decode_cache``: per-page writes into the
    pool frames, pool buffers returned for in-place paged attention.

    The cache variables must already exist with pool geometry (the
    engine builds them via ``serve.kv_slots.init_page_cache``;
    :func:`_check_pool_leaf`). Under a layer
    scan the variables are the whole STACKED leaves (models/scan.py
    carries them) and ``pv.layer`` names this layer's plane: the write
    is one scatter into it and what is returned is still the whole
    leaf, which :func:`attention` hands on with the same layer.
    """
    from pytorch_distributed_tpu.ops.paged_attention import (
        PagedKVQuant,
        paged_write,
    )

    if write_pos is None:
        raise ValueError(
            "paged decode (an active PagedView) requires write_pos — "
            "the lockstep cache_index form has no page-table row"
        )
    names = ["cached_key", "cached_value"]
    news = [k, v]
    if quantize == "int8":
        (qk, sk), (qv, sv) = _q8_rows(k), _q8_rows(v)
        names += ["cached_key_scale", "cached_value_scale"]
        news = [qk, qv, sk, sv]
    pools = [module.variable("cache", name, None) for name in names]
    _check_pool_leaf(pools[0].value, pv)
    for pool, new in zip(pools, news):
        pool.value = paged_write(
            pool.value, new, pv.page_tables, write_pos, pv.keep, pv.layer
        )
    if quantize == "int8":
        return (
            PagedKVQuant(pools[0].value, pools[2].value, k.dtype),
            PagedKVQuant(pools[1].value, pools[3].value, v.dtype),
            write_pos,
        )
    return pools[0].value, pools[1].value, write_pos


# --------------------------------------------------------------------------
# implementation dispatch: XLA einsum path vs Pallas flash kernel
# --------------------------------------------------------------------------

_IMPL = "auto"  # auto | flash | xla
# warn-once dedup for "flash" calls the kernel does not cover
_warned_flash_fallbacks: set = set()


def set_attention_impl(impl: str) -> None:
    """Select the attention backend for :func:`attention`.

    * ``"xla"``   — the einsum/softmax path above (XLA fuses it).
    * ``"flash"`` — the Pallas blocked kernel (ops/flash_attention.py).
    * ``"auto"``  — the XLA path everywhere; the Pallas kernel is opt-in
      ("flash"). What a v5e showed (jax 0.9.0, libtpu 0.0.34,
      chip_smoke.py): the kernel compiles — forward and backward, plain,
      packed and key-masked, f32 and bf16, at (B8,S1024) for 16 heads of
      64 and 32/8 heads of 128 — in seconds to tens of seconds, and
      matches this module's einsum path within reassociation error. What
      it has NOT shown is a time: neither path has a steady-state
      measurement, so nothing yet justifies moving the default. A call
      the kernel does not cover (4-D mask, bias, dropout, window, decode)
      takes the einsum path under "flash" too, and says so once.
    """
    if impl not in ("auto", "flash", "xla"):
        raise ValueError(f"unknown attention impl {impl!r}")
    global _IMPL
    if impl != _IMPL:
        _IMPL = impl
        # jit caches don't key on this flag; drop them so already-compiled
        # steps retrace with the newly selected backend
        jax.clear_caches()


def get_attention_impl() -> str:
    return _IMPL


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    mask: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    q_offset: int = 0,
    bias: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng=None,
    window: Optional[int] = None,
    bias_fn=None,
) -> jnp.ndarray:
    """Dispatching attention: models call this instead of an impl directly.

    ``bias_fn(q_pos [S], k_pos [T]) -> [Hq, S, T]`` is the
    position-COMPUTED form of ``bias`` (T5 buckets, ALiBi slopes):
    unsharded paths materialize it once over the call's positions, and
    RING sequence parallelism evaluates it per block from TRUE GLOBAL
    positions — the form that lets relative-position models (T5, ALiBi)
    run sequence-parallel without anyone materializing the full [S, T]
    bias (ulysses refuses it toward ring — see ulysses_attention).
    Mutually exclusive with ``bias``.
    """
    from pytorch_distributed_tpu.parallel.sequence import (
        sequence_parallel_attention,
        sequence_parallel_mode,
    )
    from pytorch_distributed_tpu.ops.paged_attention import (
        active_view as _paged_active_view,
        paged_attention as _paged_attention,
    )

    pv = _paged_active_view()
    if pv is not None:
        # paged decode (serve engine): k/v are the PAGE POOL buffers
        # decode_cache just wrote (int8: PagedKVQuant pairs) — stream
        # them in place, per-row causal masking from write_pos. The
        # models' call sites stay one implementation; everything the
        # paged op does not express is refused, not silently dropped.
        if (
            mask is not None or segment_ids is not None
            or bias is not None or bias_fn is not None
            or dropout_rate > 0.0
        ):
            raise NotImplementedError(
                "paged decode supports plain causal attention only "
                "(no kv_mask/segment_ids/bias/dropout — the serving "
                "engine's decode contract)"
            )
        if getattr(q_offset, "ndim", 0) != 1:
            raise ValueError(
                "paged decode requires the per-row q_offset form "
                "(decode_cache's write_pos return)"
            )
        return _paged_attention(
            q, k, v, page_tables=pv.page_tables, lengths=q_offset,
            layer=pv.layer, keep=pv.keep, scale=scale, window=window,
        )

    # q_offset may be a traced value (KV-cache decode); only a static
    # python 0 qualifies for the flash / sequence-parallel fast paths
    static_zero_offset = isinstance(q_offset, int) and q_offset == 0
    seq_axis, _ = sequence_parallel_mode()
    if seq_axis is not None and not static_zero_offset:
        # decode (traced offset) under sequence parallelism would
        # silently attend only to the local KV shard — fail loudly,
        # masked (kv_mask/prompt_mask) or not
        raise NotImplementedError(
            "KV-cache decode is not supported inside sequence-parallel "
            "mode; disable_sequence_parallel() around generation"
        )
    if seq_axis is not None and mask is None:
        if segment_ids is not None:
            # sharded ring/all-to-all attention would need the segment
            # table of REMOTE shards; silently ignoring it would leak
            # attention across documents
            raise NotImplementedError(
                "packed (segment_ids) attention is not supported inside "
                "sequence-parallel mode"
            )
        if bias is not None:
            # a MATERIALIZED bias spans the full sequence; slicing it
            # per ring shard would misalign buckets. The supported form
            # is bias_fn, evaluated per block from global positions.
            raise NotImplementedError(
                "materialized additive bias is not supported inside "
                "sequence-parallel mode — pass bias_fn(q_pos, k_pos) "
                "so each shard computes its own block"
            )
        if dropout_rate > 0.0:
            # ring/all-to-all shards would each need a coordinated rng
            # over the FULL [S, T] weight matrix; dropping locally would
            # silently decorrelate shards
            raise NotImplementedError(
                "attention-weight dropout is not supported inside "
                "sequence-parallel mode"
            )
        # sliding windows and bias_fn are exact under BOTH impls: the
        # ring carries true global positions (band + per-block bias),
        # and ulysses holds the full sequence per head subset after its
        # all-to-all; custom scales pass straight through
        return sequence_parallel_attention(
            q, k, v, causal=causal, window=window, scale=scale,
            bias_fn=bias_fn,
        )
    if bias_fn is not None:
        if bias is not None:
            raise ValueError("pass bias or bias_fn, not both")
        if getattr(q_offset, "ndim", 0) == 1:
            # bias_fn materializes ONE [Hq, S, T] block shared by the
            # batch; per-row offsets would need a per-row bias — no
            # relative-position model is in the serve zoo, so refuse
            raise NotImplementedError(
                "bias_fn does not compose with per-row q_offset "
                "(slot-pool decode)"
            )
        # unsharded: materialize once over this call's positions
        # (traced q_offset included — decode works)
        q_pos = jnp.arange(q.shape[1]) + q_offset
        k_pos = jnp.arange(k.shape[1])
        bias = bias_fn(q_pos, k_pos)[None]  # [1, Hq, S, T]
    if _IMPL == "flash":
        # the kernel covers full, causal, [B, T] key-padding masks,
        # packed segment ids, and custom softmax scales (T5's 1.0 rides
        # through as sm_scale); everything else takes the einsum path —
        # and says so once, because a run that asked for the kernel and
        # quietly timed the einsum measures the wrong thing
        why_not = None
        if getattr(mask, "ndim", 2) != 2:
            why_not = "a full 4-D mask"
        elif not static_zero_offset:
            why_not = "a non-zero or traced q_offset (KV-cache decode)"
        elif bias is not None:
            why_not = "an additive bias (T5 buckets, ALiBi)"
        elif dropout_rate != 0.0:
            why_not = "attention-weight dropout"
        elif window is not None:
            why_not = "a sliding window"
        elif q.shape[1] == 1:
            # T5 cross-attention at S=1: a blocked kernel per token is
            # all launch overhead
            why_not = "a single query position"
        if why_not is None:
            from pytorch_distributed_tpu.ops.flash_attention import (
                flash_attention,
            )

            return flash_attention(
                q, k, v, causal=causal, kv_mask=mask,
                segment_ids=segment_ids, sm_scale=scale,
            )
        if why_not not in _warned_flash_fallbacks:
            _warned_flash_fallbacks.add(why_not)
            logger.warning(
                "set_attention_impl('flash') is set, but this call has "
                "%s, which the flash kernel does not cover: it runs on "
                "the XLA einsum path (said once per reason)", why_not,
            )
    return dot_product_attention(
        q, k, v, causal=causal, mask=mask, segment_ids=segment_ids,
        q_offset=q_offset, bias=bias, scale=scale,
        dropout_rate=dropout_rate, dropout_rng=dropout_rng,
        window=window,
    )
