"""Compute ops: attention (XLA and Pallas paths), rotary embeddings.

The hot ops of the transformer recipes live here, written MXU-first:
batched einsums in bf16, f32 softmax accumulation, no data-dependent
shapes. The Pallas kernels (flash_attention.py, opt-in; paged_attention.py,
the serving engine's TPU default) sit beside the XLA paths they must match.
"""

from pytorch_distributed_tpu.ops.attention import (
    attention as scaled_dot_product_attention,  # torch-texture alias; the
    # bare name would shadow the ops.attention submodule on the package
    dot_product_attention,
    get_attention_impl,
    set_attention_impl,
    apply_rope,
    rope_frequencies,
)
from pytorch_distributed_tpu.ops.flash_attention import flash_attention
from pytorch_distributed_tpu.ops.paged_attention import (
    PagedKVQuant,
    PagedPrefix,
    PagedView,
    get_paged_attention_impl,
    paged_attention,
    paged_attention_reference,
    paged_write,
    set_paged_attention_impl,
)
from pytorch_distributed_tpu.ops.lm_loss import (
    causal_lm_chunked_loss,
    chunked_softmax_cross_entropy,
)
from pytorch_distributed_tpu.ops.quant import (
    dequantize_tree,
    QuantizedModel,
    quantize_for_scan_dequant,
    quantize_tree_int4,
    quantize_tree_int8,
    quantized_apply_fn,
    quantized_bytes,
)
from pytorch_distributed_tpu.ops.moe import (
    MoEMLP,
    collect_aux_loss,
    moe_partition_rules,
)

__all__ = [
    "dequantize_tree",
    "QuantizedModel",
    "quantize_for_scan_dequant",
    "quantize_tree_int4",
    "quantize_tree_int8",
    "quantized_apply_fn",
    "quantized_bytes",
    "MoEMLP",
    "causal_lm_chunked_loss",
    "chunked_softmax_cross_entropy",
    "collect_aux_loss",
    "moe_partition_rules",
    "scaled_dot_product_attention",
    "dot_product_attention",
    "flash_attention",
    "PagedKVQuant",
    "PagedPrefix",
    "PagedView",
    "get_paged_attention_impl",
    "paged_attention",
    "paged_attention_reference",
    "paged_write",
    "set_paged_attention_impl",
    "get_attention_impl",
    "set_attention_impl",
    "apply_rope",
    "rope_frequencies",
]
