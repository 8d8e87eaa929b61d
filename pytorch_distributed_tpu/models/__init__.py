"""Model zoo: native TPU-first implementations of the reference's recipe
models (BASELINE.json:6-12) — ResNet-18/50, BERT-base, GPT-2-medium,
Llama-3-8B — plus beyond-reference families sharing the same machinery:
ViT, T5, and the Llama-body config variants (Llama-3.1/3.2, Mistral,
Qwen2, Gemma, sparse-MoE Mixtral; see docs/MIGRATION.md "Model zoo").
All NHWC / bf16-compute / f32-params by default, written against the
framework's precision policy and partition-rule system; every family is
HF-logit-parity pinned with import AND export (interop.py).
"""

from pytorch_distributed_tpu.models.resnet import (
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from pytorch_distributed_tpu.models.bert import (
    BertConfig,
    BertModel,
    BertForMaskedLM,
    BertForSequenceClassification,
    mask_tokens,
    bert_partition_rules,
)
from pytorch_distributed_tpu.models.gpt2 import (
    GPT2Config,
    GPT2LMHead,
    gpt2_partition_rules,
)
from pytorch_distributed_tpu.models.vit import (
    ViT,
    ViTConfig,
    vit_partition_rules,
)
from pytorch_distributed_tpu.models.t5 import (
    T5Config,
    T5ForConditionalGeneration,
    generate_encdec,
    shift_right,
    t5_partition_rules,
)
from pytorch_distributed_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    RopeScaling,
    llama_partition_rules,
)
from pytorch_distributed_tpu.models.mistral import (
    MistralConfig,
    MistralForCausalLM,
    mistral_partition_rules,
)
from pytorch_distributed_tpu.models.gemma import (
    GemmaConfig,
    GemmaForCausalLM,
    gemma_partition_rules,
)
from pytorch_distributed_tpu.models.neox import (
    NeoXConfig,
    NeoXForCausalLM,
    neox_partition_rules,
)
from pytorch_distributed_tpu.models.phi3 import (
    Phi3Config,
    Phi3ForCausalLM,
    phi3_partition_rules,
)
from pytorch_distributed_tpu.models.qwen2 import (
    Qwen2Config,
    Qwen2ForCausalLM,
    qwen2_partition_rules,
)
from pytorch_distributed_tpu.models.qwen3 import (
    Qwen3Config,
    Qwen3ForCausalLM,
    qwen3_partition_rules,
)
from pytorch_distributed_tpu.models.deepseek_v3 import (
    DeepseekV3Config,
    DeepseekV3ForCausalLM,
    deepseek_v3_partition_rules,
)
from pytorch_distributed_tpu.models.mixtral import (
    MixtralConfig,
    MixtralForCausalLM,
    mixtral_partition_rules,
)

__all__ = [
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "BertConfig",
    "BertModel",
    "BertForMaskedLM",
    "BertForSequenceClassification",
    "mask_tokens",
    "bert_partition_rules",
    "GPT2Config",
    "GPT2LMHead",
    "gpt2_partition_rules",
    "LlamaConfig",
    "LlamaForCausalLM",
    "RopeScaling",
    "MistralConfig",
    "MistralForCausalLM",
    "mistral_partition_rules",
    "GemmaConfig",
    "GemmaForCausalLM",
    "gemma_partition_rules",
    "NeoXConfig",
    "NeoXForCausalLM",
    "neox_partition_rules",
    "Phi3Config",
    "Phi3ForCausalLM",
    "phi3_partition_rules",
    "Qwen2Config",
    "Qwen2ForCausalLM",
    "qwen2_partition_rules",
    "Qwen3Config",
    "Qwen3ForCausalLM",
    "qwen3_partition_rules",
    "DeepseekV3Config",
    "DeepseekV3ForCausalLM",
    "deepseek_v3_partition_rules",
    "MixtralConfig",
    "MixtralForCausalLM",
    "mixtral_partition_rules",
    "llama_partition_rules",
    "T5Config",
    "T5ForConditionalGeneration",
    "generate_encdec",
    "shift_right",
    "t5_partition_rules",
    "ViT",
    "ViTConfig",
    "vit_partition_rules",
]
