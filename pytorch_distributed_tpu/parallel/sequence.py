"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Long-context training shards the *sequence* axis over the ``sp`` mesh axis.
Everything in a transformer is pointwise over sequence except attention, so
XLA's sharding propagation handles the whole model except the softmax over
keys — which, left to the compiler, becomes an all-gather of full K/V
(O(S) memory per chip again). The two standard fixes, both implemented
here as ``shard_map`` collectives over ``sp``:

* **Ring attention** (Liu et al. 2023 pattern): keep Q local, rotate K/V
  shards around the ring with ``lax.ppermute``, combining per-step partial
  attention with the online-softmax rule. Peak memory O(S/sp); the
  rotation overlaps with the block computation on ICI.
* **Ulysses / all-to-all** (DeepSpeed-Ulysses pattern): ``lax.all_to_all``
  re-shards [B, S/sp, H, D] -> [B, S, H/sp, D], runs ordinary (flash)
  attention per head subset, and transforms back. Cheaper collectives for
  moderate S; requires heads divisible by sp.

The reference (a DDP/FSDP recipe collection, SURVEY.md §2) has no
sequence parallelism; this is a first-class capability of the TPU-native
framework (long-context training is mesh-axis cheap under SPMD).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_tpu.runtime.mesh import current_mesh, data_axes

_NEG_INF = -1e30


def _block_attn_parts(
    q: jnp.ndarray,  # [B, S, Hq, D] local queries
    k: jnp.ndarray,  # [B, T, Hkv, D] one ring step's keys
    v: jnp.ndarray,
    q_pos: jnp.ndarray,  # [S] global positions of local queries
    k_pos: jnp.ndarray,  # [T] global positions of this step's keys
    causal: bool,
    scale: float,
    window=None,
    bias=None,  # [Hkv*G(local), S, T] — already head-sliced by caller
):
    """Unnormalized block attention: (o=[B,S,Hkv,G,D] f32, m, l=[B,Hkv,G,S,1]).

    ``window``: sliding-window band on top of causal — the ring carries
    TRUE GLOBAL positions for both sides, so the band is exact across
    shard boundaries (slot-index banding would be wrong here).
    """
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    logits = (
        jnp.einsum("bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32)
        * scale
    )  # [B, Hkv, G, S, T]
    if bias is not None:
        logits = logits + bias.reshape(Hkv, G, S, T)[None].astype(
            jnp.float32
        )
    mask = None
    if causal or window is not None:
        mask = q_pos[:, None] >= k_pos[None, :]  # [S, T]
        if window is not None:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)  # [B,Hkv,G,S,1]
    p = jnp.exp(logits - m)
    if mask is not None:
        # a fully-masked block has m == -inf and exp(0) == 1 everywhere;
        # re-apply the mask on p so it contributes nothing
        p = jnp.where(mask[None, None, None], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return o, m, l


def _ring_attention_local(
    q, k, v, *, axis_name: str, causal: bool, scale: float, window=None,
    bias_fn=None,
):
    """Runs inside shard_map: q/k/v are the local sequence shards."""
    B, S, Hq, D = q.shape
    T = k.shape[1]
    Hkv = k.shape[2]
    G = Hq // Hkv
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_pos = my * S + jnp.arange(S)
    # heads may additionally be sharded over tp: bias_fn returns GLOBAL
    # heads, so slice this chip's subset once
    tp_i = lax.axis_index("tp")
    h_loc = Hq

    def block_bias(k_pos):
        if bias_fn is None:
            return None
        full = bias_fn(q_pos, k_pos)  # [Hq_global, S, T]
        return lax.dynamic_slice_in_dim(full, tp_i * h_loc, h_loc, 0)

    def accumulate(t, acc, k_t, v_t):
        o_acc, m_acc, l_acc = acc
        src = (my - t) % n  # whose K/V shard we hold at step t
        k_pos = src * T + jnp.arange(T)
        o_t, m_t, l_t = _block_attn_parts(
            q, k_t, v_t, q_pos, k_pos, causal, scale, window,
            block_bias(k_pos),
        )
        m_new = jnp.maximum(m_acc, m_t)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_t - m_new)
        l_new = l_acc * alpha + l_t * beta
        # o carries [B,S,Hkv,G,D]; scale factors are [B,Hkv,G,S,1]
        scale_o = lambda o, f: o * f[..., 0].transpose(0, 3, 1, 2)[..., None]
        o_new = scale_o(o_acc, alpha) + scale_o(o_t, beta)
        return o_new, m_new, l_new

    def step(t, carry):
        acc, k_t, v_t = carry
        acc = accumulate(t, acc, k_t, v_t)
        # rotate K/V to the next rank (overlaps with the next block's matmul)
        k_next = lax.ppermute(k_t, axis_name, perm)
        v_next = lax.ppermute(v_t, axis_name, perm)
        return acc, k_next, v_next

    o0 = jnp.zeros((B, S, Hkv, G, D), jnp.float32)
    m0 = jnp.full((B, Hkv, G, S, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, S, 1), jnp.float32)
    # n-1 compute+rotate steps, then a final compute on the last-held
    # shard — no rotation whose result nobody reads
    acc, k_last, v_last = lax.fori_loop(0, n - 1, step, ((o0, m0, l0), k, v))
    o, m, l = accumulate(n - 1, acc, k_last, v_last)
    l_bskg = l[..., 0].transpose(0, 3, 1, 2)[..., None]  # [B,S,Hkv,G,1]
    out = o / jnp.where(l_bskg > 0, l_bskg, 1.0)
    return out.reshape(B, S, Hq, D).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, S, Hq, D] globally; S sharded over ``axis``
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    axis: str = "sp",
    mesh: Optional[Mesh] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    bias_fn=None,
) -> jnp.ndarray:
    """Exact attention with K/V rotated around the ``axis`` ring.

    Call on *global* arrays under jit; shard_map partitions S over ``axis``
    (batch over the data axes, heads over ``tp``) and the ring keeps every
    chip's K/V working set at S/sp. ``window`` adds the sliding-window
    band (Mistral) over true global positions — exact across shard
    boundaries.
    """
    mesh = mesh or current_mesh()
    if window is not None and window <= 0:
        raise ValueError(f"window must be positive, got {window}")
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P(data_axes(), axis, "tp", None)
    fn = jax.shard_map(
        functools.partial(
            _ring_attention_local, axis_name=axis, causal=causal,
            scale=scale, window=window, bias_fn=bias_fn,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool, inner):
    # (bias_fn head slicing happens in the inner closure — it needs the
    # sp index, bound here by shard_map)
    """all_to_all S<->H re-shard; runs inside shard_map."""
    # [B, S/sp, H, D] -> [B, S, H/sp, D]: after the re-shard each chip
    # holds the FULL sequence for its head subset, so any sequence-wise
    # mask (causal, sliding window) applies exactly as in the unsharded
    # op — the inner closure carries it
    a2a = lambda x: lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)
    inv = lambda x: lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)
    out = inner(a2a(q), a2a(k), a2a(v), causal)
    return inv(out)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    axis: str = "sp",
    mesh: Optional[Mesh] = None,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    bias_fn=None,
) -> jnp.ndarray:
    """DeepSpeed-Ulysses-style sequence parallelism: two all-to-alls around
    an ordinary full-sequence attention on a head subset. Heads (q and kv)
    must be divisible by the ``axis`` size. ``window`` = sliding-window
    band (each chip sees the full sequence post-re-shard, so the band
    applies exactly). ``bias_fn`` is REFUSED here: the fn returns
    GLOBAL heads, so each chip would materialize [Hq_global, S, S]
    before slicing its subset — a tp*sp-factor memory overshoot in
    exactly the long-S regime SP exists for; ring evaluates the bias
    per block at [Hq_local, S/sp, S/sp] instead. Use ``impl="ring"``
    for relative-bias models."""
    if bias_fn is not None:
        raise NotImplementedError(
            "bias_fn under ulysses would materialize the full "
            "global-head [S, S] bias on every chip before head-slicing "
            "— use sequence_parallel(impl='ring'), which evaluates the "
            "bias per block from global positions"
        )
    mesh = mesh or current_mesh()
    sp = mesh.shape[axis]
    tp = mesh.shape.get("tp", 1)
    # heads are already split over tp by the spec; sp divides what remains
    Hq, Hkv = q.shape[2] // tp, k.shape[2] // tp
    if sp > 1 and (Hq == 0 or Hkv == 0 or Hq % sp or Hkv % sp):
        raise ValueError(
            f"ulysses needs per-tp-shard heads divisible by sp={sp}; got "
            f"q={Hq}, kv={Hkv} after tp={tp} "
            f"(use ring_attention for head-indivisible configs)"
        )

    def inner(q, k, v, causal):
        # The post-all-to-all local attention (full sequence, head subset)
        # picks the flash kernel when selected. NOT the attention()
        # dispatcher: sequence-parallel mode is still active here, and
        # re-entering it would recurse into ulysses with the local
        # (already head-sharded) shapes.
        from pytorch_distributed_tpu.ops.attention import (
            dot_product_attention,
            get_attention_impl,
        )

        if (
            window is None and scale is None
            and get_attention_impl() == "flash"
        ):
            from pytorch_distributed_tpu.ops.flash_attention import (
                flash_attention,
            )

            return flash_attention(q, k, v, causal=causal)
        return dot_product_attention(
            q, k, v, causal=causal, window=window, scale=scale
        )

    spec = P(data_axes(), axis, "tp", None)
    fn = jax.shard_map(
        functools.partial(
            _ulysses_local, axis_name=axis, causal=causal, inner=inner
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


# --------------------------------------------------------------------------
# model-transparent activation: ops.attention.attention() consults this
# --------------------------------------------------------------------------

_SEQ_MODE: Tuple[Optional[str], str] = (None, "ring")  # (axis or None, impl)


def enable_sequence_parallel(axis: str = "sp", impl: str = "ring") -> None:
    """Route all model attention through sequence-parallel attention.

    With this set, transformer models need no code changes: activations
    stay sequence-sharded end-to-end (XLA propagates the ``sp`` sharding
    through the pointwise/matmul ops) and the attention dispatcher wraps
    the only cross-sequence op in ring/ulysses shard_map.
    """
    if impl not in ("ring", "ulysses"):
        raise ValueError(f"unknown sequence-parallel impl {impl!r}")
    global _SEQ_MODE
    if _SEQ_MODE != (axis, impl):
        _SEQ_MODE = (axis, impl)
        # jit caches don't key on this mode; retrace compiled steps
        jax.clear_caches()


def disable_sequence_parallel() -> None:
    global _SEQ_MODE
    if _SEQ_MODE[0] is not None:
        _SEQ_MODE = (None, "ring")
        jax.clear_caches()


@contextlib.contextmanager
def sequence_parallel(axis: str = "sp", impl: str = "ring"):
    """Context manager form of enable/disable_sequence_parallel."""
    prev = _SEQ_MODE
    enable_sequence_parallel(axis, impl)
    try:
        yield
    finally:
        if prev[0] is None:
            disable_sequence_parallel()
        else:
            enable_sequence_parallel(*prev)


def sequence_parallel_mode() -> Tuple[Optional[str], str]:
    return _SEQ_MODE


def sequence_parallel_attention(
    q, k, v, *, causal: bool, window=None, scale=None, bias_fn=None
) -> jnp.ndarray:
    axis, impl = _SEQ_MODE
    assert axis is not None
    if impl == "ring":
        return ring_attention(q, k, v, causal=causal, axis=axis,
                              window=window, scale=scale, bias_fn=bias_fn)
    return ulysses_attention(q, k, v, causal=causal, axis=axis,
                             window=window, scale=scale, bias_fn=bias_fn)
