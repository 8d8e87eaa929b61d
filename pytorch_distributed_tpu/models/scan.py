"""Scan-over-layers: compile ONE transformer block, not ``num_layers``.

The reference's eager CUDA modules pay nothing for Python-unrolled layer
stacks; under XLA an unrolled stack multiplies trace/compile time by depth
(GPT-2-medium = 24 copies of the same HLO) and bloats the program. The
TPU-idiomatic layout is ``lax.scan`` over the depth axis — via ``nn.scan``
so the block's params stack to ``[L, ...]``:

* compile time is O(1) in depth,
* sharding rules see one stacked tensor per weight (FSDP shards a dim of
  it; TP rules adapt via ``parallel.sharding.stacked``),
* pipeline parallelism consumes the stacked layout directly (stage dim =
  groups of layers, ``parallel/pipeline.py``).

``remat=True`` wraps the block in ``nn.remat`` so the backward pass
recomputes each block's activations instead of storing them — the standard
HBM/FLOPs trade for long sequences (jax.checkpoint). ``cfg.remat_policy``
refines the trade: ``"full"`` recomputes everything; ``"dots"`` saves
matmul outputs and recomputes only the cheap elementwise/softmax work
(jax.checkpoint_policies) — faster backward, a few activations more HBM.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple, Type

import flax.linen as nn
import jax.numpy as jnp


def remat_policy(name: Optional[str]):
    """jax.checkpoint policy by short name: 'full' (recompute everything),
    'dots' (save all matmul results), 'dots_no_batch' (save weight-matmul
    results, recompute batched attention products)."""
    import jax

    if name in (None, "full"):
        return None  # nothing saved — maximum recompute
    if name == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if name == "dots_no_batch":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    raise ValueError(
        f"unknown remat_policy {name!r}; expected full | dots | "
        f"dots_no_batch"
    )


def scan_stack(
    block_cls: Type[nn.Module],
    cfg,
    *,
    length: Optional[int] = None,
    remat: Optional[bool] = None,
    static_argnums: Tuple[int, ...] = (),
    name: str = "blocks",
    with_layer: bool = False,
) -> Callable:
    """Build the scanned stack and return ``f(x, *bcast) -> x``.

    Must be called inside the parent module's ``@nn.compact`` ``__call__``
    (the scanned module attaches to the caller's scope under ``name``).
    ``block_cls(cfg).__call__(x, *bcast)`` takes the carried activation
    first; every further argument is broadcast unchanged to all layers.
    Under ``remat``, pass ``static_argnums`` (0 = ``x``) marking python-bool
    args like ``deterministic`` so they stay static.

    ``cfg.scan_dequant`` wraps the block in ``nn.map_variables`` so a
    QUANTIZED stacked param tree (ops/quant.py int8/int4 leaf dicts,
    leading ``[L]`` axis — exactly what ``quantize_tree_int8/int4``
    produce on the stacked kernels) dequantizes PER LAYER inside each
    scan iteration, never materializing the whole reconstructed stack:
    peak weight residency is quantized-tree + ONE layer's bf16 weights.
    This is what lets an int4 8B (~4.5 GB at rest) decode on a single
    16 GB chip — whole-tree ``quantized_apply_fn`` would transiently
    need the full ~16 GB bf16 reconstruction. Plain (unquantized)
    leaves pass through untouched, so initializing with the flag on
    still works and quantization stays a post-training transform.

    Under an active ``ops.paged_attention.PagedView`` (the serving
    engine's paged programs) the ``cache`` collection is the page pool,
    and it rides the loop as a CARRY, whole, instead of being sliced
    per layer and stacked back: an XLA ``while`` cannot alias an ``xs``
    slice with a ``ys`` slice, so a scanned pool leaf is copied through
    the loop on every call. The loop counts its own layers
    beside the activation and names each to the view
    (``paged_layer``), which is how ``decode_cache`` and ``attention``
    find their plane — the blocks, and the models, learn nothing of it.

    ``with_layer`` hands every block its own index in the stack as the
    argument after ``x`` (a traced int32 scalar): for a block that reads
    a leaf the loop must NOT slice a layer — a kernel's operand is
    copied out of a scanned leaf every iteration, where a broadcast
    ``[L, ...]`` leaf indexed inside the kernel stays where it lies
    (``ops/moe.py``'s expert weights).
    """
    from pytorch_distributed_tpu.ops.paged_attention import (
        active_view,
        paged_layer,
    )

    use_remat = cfg.remat if remat is None else remat
    paged = active_view() is not None
    counted = paged or with_layer

    class Body(nn.Module):
        @nn.compact
        def __call__(self, carry, *bcast):
            block = block_cls(cfg, name="block")
            if not counted:
                return block(carry, *bcast), None
            x, layer = carry
            args = (layer,) + bcast if with_layer else bcast
            if paged:
                with paged_layer(layer):
                    x = block(x, *args)
            else:
                x = block(x, *args)
            return (x, layer + 1), None

    if getattr(cfg, "scan_dequant", False):
        from pytorch_distributed_tpu.ops.quant import dequantize_tree
        from pytorch_distributed_tpu.runtime.precision import (
            current_policy,
        )

        def _dequant_in(vars_in):
            policy = current_policy()
            return dequantize_tree(vars_in, dtype=policy.param_dtype)

        Body = nn.map_variables(
            Body, "params",
            trans_in_fn=_dequant_in,
            # init path: params created inside are plain arrays; store
            # them unchanged (quantization happens outside, later)
            trans_out_fn=lambda v: v,
            mutable=True,
        )

    body = (
        nn.remat(
            Body,
            prevent_cse=False,
            static_argnums=static_argnums,
            policy=remat_policy(cfg.remat_policy),
        )
        if use_remat
        else Body
    )
    # cache: per-layer KV decode caches stack [L, ...] like params (a
    # page pool is carried instead, see above); intermediates:
    # per-layer sown values (e.g. MoE aux losses)
    axes = {"params": 0, "cache": 0, "intermediates": 0}
    if paged:
        del axes["cache"]
    mod = nn.scan(
        body,
        variable_axes=axes,
        variable_carry="cache" if paged else False,
        split_rngs={"params": True, "dropout": True},
        in_axes=nn.broadcast,
        length=length if length is not None else cfg.num_layers,
    )(name=name)

    def apply_stack(x, *bcast):
        if counted:
            (y, _), _ = mod((x, jnp.zeros((), jnp.int32)), *bcast)
        else:
            y, _ = mod(x, *bcast)
        return y

    return apply_stack
