"""Flash attention as a Pallas TPU kernel (fwd + custom-VJP bwd).

The reference's transformer recipes (BERT/GPT-2/Llama, BASELINE.json:9-11)
lean on cuDNN/FlashAttention CUDA kernels via ``scaled_dot_product_attention``.
The TPU-native equivalent is a Pallas kernel: blocked online-softmax
attention that never materializes the [S, T] score matrix in HBM —
O(S) memory instead of O(S^2), with f32 accumulation on the MXU.

Design (standard TPU flash schedule):

* grid = (batch*heads, q_blocks, k_blocks); the k dimension is innermost
  and sequential ("arbitrary"), so VMEM scratch (acc, running max m,
  running sum l) persists across k steps — the online-softmax carry.
* Causal masking skips the compute for fully-masked blocks via
  ``pl.when`` (blocks still iterate; skipping grid steps needs no-op
  reads anyway) and applies an elementwise mask on the diagonal blocks.
* Grouped-query attention: KV arrays are indexed per *query* head via
  the BlockSpec index_map (``kv_head = q_head * Hkv // Hq``) — no
  repeat/materialization of KV to Q heads, matching
  ``ops.attention.dot_product_attention``'s einsum design.
* Backward recomputes the blocked scores from the saved logsumexp
  (no S^2 residuals): a dq kernel with the same schedule, and a dkv
  kernel with q innermost. GQA grads for K/V are emitted per q-head and
  group-summed outside the kernel (G is small; this keeps kernel
  outputs race-free across the parallel head grid dim).
* On non-TPU backends every pallas_call runs ``interpret=True`` so the
  whole stack (and CI, per tests/conftest.py) works on the 8-device CPU
  mesh.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # large-but-finite: avoids NaN from (-inf) - (-inf)
# lse/delta carry a small replicated trailing dim: 8 == sublane tile floor,
# the minimum that satisfies TPU block tiling without 128x HBM blow-up
_LANES = 8


def _mxu_dot(a, b, a_dim: int, b_dim: int):
    """``a`` x ``b`` contracting ``a_dim`` with ``b_dim``, accumulated in
    f32. Operands narrower than f32 pin the DEFAULT precision: under a
    caller's ``jax.default_matmul_precision("highest")`` the dot would
    otherwise ask Mosaic for an fp32-contract matmul on bf16 operands,
    which it refuses ("Bad lhs type"; v5e, libtpu 0.0.34). f32 operands
    keep following the caller's precision."""
    f32 = a.dtype == jnp.float32 and b.dtype == jnp.float32
    return jax.lax.dot_general(
        a, b, (((a_dim,), (b_dim,)), ((), ())),
        precision=None if f32 else jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )


def _causal_live(q_start: int, k_start: int, block_q: int):
    """Block participates iff its last q row can see the block's first k."""
    return q_start + block_q - 1 >= k_start


def _causal_mask(s, q_start, k_start, block_q: int, block_k: int):
    """Mask scores above the causal diagonal (shared by fwd/dq/dkv)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return jnp.where((q_start + rows) >= (k_start + cols), s, _NEG_INF)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pick_block(seq: int, want: int, align: int) -> int:
    """Largest divisor of ``seq`` that is <= ``want`` and a multiple of
    ``align``; the whole of ``seq`` when there is none. The TPU lowering
    takes a block dim only if it is tile-aligned (``align``: 8 rows on
    sublanes, 128 on lanes) or spans the array."""
    if seq <= want:
        return seq
    for b in range(want - want % align, 0, -align):
        if seq % b == 0:
            return b
    return seq


def _blocks(S, T, block_q, block_k, side_inputs: bool):
    # k positions sit on LANES of the [B, 1, T] mask/segment rows, so
    # their block must be 128-aligned there; on sublanes 8 rows is the
    # floor for 4-byte types and 16 covers the packed 2-byte ones too
    return (
        _pick_block(S, block_q, 16),
        _pick_block(T, block_k, 128 if side_inputs else 16),
    )


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _seg_mask(s, qseg_ref, kseg_ref):
    """Mask scores across segment boundaries (packed sequences): query
    ids ride as a lane-replicated column [bq, _LANES], key ids as a row
    [1, bk], so the compare broadcasts with no in-kernel transpose."""
    return jnp.where(qseg_ref[0][:, :1] == kseg_ref[0], s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                has_bias: bool, has_segments: bool):
    # bias/segments are STATIC specializations: the dominant unmasked
    # (causal-LM) path carries neither input — no HBM zeros, no per-block
    # DMA, no dead VPU work
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    if has_segments:
        qseg_ref, kseg_ref = rest.pop(0), rest.pop(0)
    else:
        qseg_ref = kseg_ref = None
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    live = _causal_live(q_start, k_start, block_q) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]  # [bk, d]
        s = _mxu_dot(q, k, 1, 1) * sm_scale  # [bq, bk]
        if bias_ref is not None:  # kv padding: additive [1, bk] bias row
            s = s + bias_ref[0]
        if qseg_ref is not None:  # packed sequences: block-diagonal mask
            s = _seg_mask(s, qseg_ref, kseg_ref)

        if causal:
            s = _causal_mask(s, q_start, k_start, block_q, block_k)

        m_prev = m_ref[:, :1]  # [bq, 1] (lanes replicated)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # rescale of old accumulator
        p = jnp.exp(s - m_new)  # [bq, bk]
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + _mxu_dot(
            p.astype(v.dtype), v, 1, 0
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[:] / safe).astype(o_ref.dtype)
        # logsumexp for the backward recompute; lane-replicated to 128 so
        # the output block meets the TPU (8, 128) tiling floor
        lse_ref[0] = jnp.broadcast_to(
            m_ref[:, :1] + jnp.log(safe), lse_ref.shape[1:]
        )


def _kv_head_map(bh, hq: int, hkv: int):
    """Flat (batch*q_head) index -> flat (batch*kv_head) index."""
    return (bh // hq) * hkv + (bh % hq) * hkv // hq


def _flash_forward(q, k, v, bias, segments, *, hq, hkv, sm_scale, causal,
                   block_q, block_k):
    """q: [B*Hq, S, D]; k, v: [B*Hkv, T, D]; bias: [B, 1, T] f32
    additive or None; segments: (query ids [B, S, _LANES], key ids
    [B, 1, S]) i32 or None (self-attention packing)
    -> (out [B*Hq, S, D], lse)."""
    BH, S, D = q.shape
    _, T, _ = k.shape
    bq, bk = _blocks(
        S, T, block_q, block_k, bias is not None or segments is not None
    )
    grid = (BH, S // bq, T // bk)

    kv_map = lambda bh, qi, ki: (_kv_head_map(bh, hq, hkv), ki, 0)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        block_k=bk, has_bias=bias is not None,
        has_segments=segments is not None,
    )
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, bk, D), kv_map),
        pl.BlockSpec((1, bk, D), kv_map),
    ]
    inputs = [q, k, v]
    side_specs, side_inputs = _side_inputs(
        bias, segments, bq, bk,
        q_map=lambda bh, qi, ki: (bh // hq, qi, 0),
        k_map=lambda bh, qi, ki: (bh // hq, 0, ki),
    )
    in_specs += side_specs
    inputs += side_inputs
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),  # acc
            pltpu.VMEM((bq, 128), jnp.float32),  # running max (lanes
            # replicated)
            pltpu.VMEM((bq, 128), jnp.float32),  # running sum
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
        name="flash_attention_fwd",
    )(*inputs)
    return out, lse


def _side_inputs(bias, segments, bq, bk, *, q_map, k_map):
    """(specs, inputs) for the optional mask-bias row and segment ids,
    in the order the kernels pop them. Both are per-BATCH side inputs;
    a (1, bk) block over a [B, T] array would put the batch on sublanes
    in a 1-row block the TPU lowering refuses for B > 1, hence the
    [B, 1, T] rows (block (1, 1, bk): whole on sublanes, 128-aligned on
    lanes) and the lane-replicated [B, S, _LANES] query-id column."""
    specs, inputs = [], []
    if bias is not None:
        specs.append(pl.BlockSpec((1, 1, bk), k_map))
        inputs.append(bias)
    if segments is not None:
        specs.append(pl.BlockSpec((1, bq, _LANES), q_map))
        specs.append(pl.BlockSpec((1, 1, bk), k_map))
        inputs.extend(segments)
    return specs, inputs


# the innermost grid dimension carries the online-softmax (or gradient)
# accumulator in VMEM scratch, so it is sequential ("arbitrary")
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               sm_scale, causal, block_q, block_k, has_bias, has_segments):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    if has_segments:
        qseg_ref, kseg_ref = rest.pop(0), rest.pop(0)
    else:
        qseg_ref = kseg_ref = None
    dq_ref, acc_ref = rest
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    live = _causal_live(q_start, k_start, block_q) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]  # [bq, 1] (lanes replicated)
        delta = delta_ref[0][:, :1]
        s = _mxu_dot(q, k, 1, 1) * sm_scale
        if bias_ref is not None:
            s = s + bias_ref[0]
        if qseg_ref is not None:
            s = _seg_mask(s, qseg_ref, kseg_ref)
        if causal:
            s = _causal_mask(s, q_start, k_start, block_q, block_k)
        p = jnp.exp(s - lse)  # [bq, bk]
        dp = _mxu_dot(do, v.astype(jnp.float32), 1, 1)
        ds = p * (dp - delta) * sm_scale
        acc_ref[:] += _mxu_dot(ds.astype(k.dtype), k, 1, 0)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                sm_scale, causal, block_q, block_k, has_bias, has_segments):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    if has_segments:
        qseg_ref, kseg_ref = rest.pop(0), rest.pop(0)
    else:
        qseg_ref = kseg_ref = None
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    live = _causal_live(q_start, k_start, block_q) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]  # [bq, 1] (lanes replicated)
        delta = delta_ref[0][:, :1]
        s = _mxu_dot(q, k, 1, 1) * sm_scale
        if bias_ref is not None:
            s = s + bias_ref[0]
        if qseg_ref is not None:
            s = _seg_mask(s, qseg_ref, kseg_ref)
        if causal:
            s = _causal_mask(s, q_start, k_start, block_q, block_k)
        p = jnp.exp(s - lse)  # [bq, bk]
        dv_acc[:] += _mxu_dot(p.astype(do.dtype), do, 0, 0)  # [bk, d]
        dp = _mxu_dot(do, v.astype(jnp.float32), 1, 1)
        ds = p * (dp - delta) * sm_scale  # [bq, bk]
        dk_acc[:] += _mxu_dot(ds.astype(q.dtype), q, 0, 0)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# --------------------------------------------------------------------------
# public op
# --------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8)
)
def _flash(q, k, v, bias, segments, sm_scale, causal, block_q, block_k):
    out, _lse = _fwd(
        q, k, v, bias, segments, sm_scale, causal, block_q, block_k
    )
    return out


def _fwd(q, k, v, bias, segments, sm_scale, causal, block_q, block_k):
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    out, lse = _flash_forward(
        qf, kf, vf, bias, segments, hq=Hq, hkv=Hkv, sm_scale=sm_scale,
        causal=causal, block_q=block_q, block_k=block_k,
    )
    return out.reshape(B, Hq, S, D).transpose(0, 2, 1, 3), lse


def _flash_fwd(q, k, v, bias, segments, sm_scale, causal, block_q, block_k):
    out, lse = _fwd(
        q, k, v, bias, segments, sm_scale, causal, block_q, block_k
    )
    return out, (q, k, v, bias, segments, out, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, res, dout):
    q, k, v, bias, segments, out, lse = res
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    G = Hq // Hkv

    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    dof = dout.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    of = out.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    # delta_i = rowsum(dO_i * O_i) — the softmax-grad correction term,
    # lane-replicated like lse to satisfy TPU block tiling
    delta = jnp.broadcast_to(
        jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)[
            ..., None
        ],
        (B * Hq, S, _LANES),
    )

    BH = B * Hq
    has_bias = bias is not None
    has_segments = segments is not None
    bq, bk = _blocks(S, T, block_q, block_k, has_bias or has_segments)
    kv_map = lambda bh, qi, ki: (_kv_head_map(bh, Hq, Hkv), ki, 0)
    q_map = lambda bh, qi, ki: (bh, qi, 0)
    lse_map = lambda bh, qi, ki: (bh, qi, 0)

    dq_specs = [
        pl.BlockSpec((1, bq, D), q_map),
        pl.BlockSpec((1, bk, D), kv_map),
        pl.BlockSpec((1, bk, D), kv_map),
        pl.BlockSpec((1, bq, D), q_map),
        pl.BlockSpec((1, bq, _LANES), lse_map),
        pl.BlockSpec((1, bq, _LANES), lse_map),
    ]
    side_specs, side_inputs = _side_inputs(
        bias, segments, bq, bk,
        q_map=lambda bh, qi, ki: (bh // Hq, qi, 0),
        k_map=lambda bh, qi, ki: (bh // Hq, 0, ki),
    )
    dq_inputs = [qf, kf, vf, dof, lse, delta] + side_inputs
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, has_bias=has_bias,
            has_segments=has_segments,
        ),
        grid=(BH, S // bq, T // bk),
        in_specs=dq_specs + side_specs,
        out_specs=pl.BlockSpec((1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
        name="flash_attention_dq",
    )(*dq_inputs)

    # dk/dv per *query* head (race-free), group-summed to kv heads after
    kv_q_map = lambda bh, ki, qi: (_kv_head_map(bh, Hq, Hkv), ki, 0)
    dkv_specs = [
        pl.BlockSpec((1, bq, D), lambda bh, ki, qi: (bh, qi, 0)),
        pl.BlockSpec((1, bk, D), kv_q_map),
        pl.BlockSpec((1, bk, D), kv_q_map),
        pl.BlockSpec((1, bq, D), lambda bh, ki, qi: (bh, qi, 0)),
        pl.BlockSpec((1, bq, _LANES), lambda bh, ki, qi: (bh, qi, 0)),
        pl.BlockSpec((1, bq, _LANES), lambda bh, ki, qi: (bh, qi, 0)),
    ]
    side_specs, side_inputs = _side_inputs(
        bias, segments, bq, bk,
        q_map=lambda bh, ki, qi: (bh // Hq, qi, 0),
        k_map=lambda bh, ki, qi: (bh // Hq, 0, ki),
    )
    dkv_inputs = [qf, kf, vf, dof, lse, delta] + side_inputs
    dk_per_q, dv_per_q = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, has_bias=has_bias,
            has_segments=has_segments,
        ),
        grid=(BH, T // bk, S // bq),
        in_specs=dkv_specs + side_specs,
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), k.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32)] * 2,
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
        name="flash_attention_dkv",
    )(*dkv_inputs)

    dq = dq.reshape(B, Hq, S, D).transpose(0, 2, 1, 3)
    dk = (
        dk_per_q.reshape(B, Hkv, G, T, D).sum(axis=2)
        .transpose(0, 2, 1, 3)
    )
    dv = (
        dv_per_q.reshape(B, Hkv, G, T, D).sum(axis=2)
        .transpose(0, 2, 1, 3)
    )
    # bias comes from a boolean padding mask and segments are ids — both
    # non-differentiable sources; zero/None cotangents are correct
    return (
        dq, dk, dv,
        None if bias is None else jnp.zeros_like(bias),
        None,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,  # [B, S, Hq, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,  # [B, T, Hkv, D]
    *,
    causal: bool = False,
    kv_mask: Optional[jnp.ndarray] = None,  # [B, T] bool, True = attend
    segment_ids: Optional[jnp.ndarray] = None,  # [B, S] i32, packing
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jnp.ndarray:
    """Blocked flash attention; drop-in for
    :func:`~pytorch_distributed_tpu.ops.attention.dot_product_attention`
    for full, causal, key-padding-masked (``kv_mask``, the BERT-style
    [B, T] mask), and PACKED attention (``segment_ids``: tokens attend
    only within their own segment — the MaxText-style fixed-shape
    document packing; self-attention only). Returns [B, S, Hq, D] in
    q.dtype.

    Rows whose keys are ENTIRELY masked produce finite but undefined
    outputs (so does the XLA path: softmax over all -inf is uniform);
    real padding always leaves >= 1 valid token per sequence."""
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"query heads {Hq} not a multiple of kv heads {Hkv}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    bias = None
    if kv_mask is not None:
        if kv_mask.shape != (B, T):
            raise ValueError(
                f"kv_mask must be [batch, kv_len] = {(B, T)}, "
                f"got {kv_mask.shape}"
            )
        bias = jnp.where(kv_mask.astype(jnp.bool_), 0.0, _NEG_INF).astype(
            jnp.float32
        )[:, None, :]
    if segment_ids is not None:
        if S != T:
            raise ValueError("segment_ids requires self-attention (S == T)")
        if segment_ids.shape != (B, S):
            raise ValueError(
                f"segment_ids must be [batch, seq] = {(B, S)}, "
                f"got {segment_ids.shape}"
            )
        ids = segment_ids.astype(jnp.int32)
        segment_ids = (
            jnp.broadcast_to(ids[:, :, None], (B, S, _LANES)),
            ids[:, None, :],
        )
    return _flash(
        q, k, v, bias, segment_ids, sm_scale, causal, block_q, block_k
    )
