"""Model zoo: TPU-first flax implementations with mesh sharding rules
(bert/gpt2/gptneox/t5/llama/mistral/joyai_llm_flash/jamba/lfm2_moe/granitemoehybrid/evabyte/laguna/qwen2/qwen3/olmo2/gemma/gemma2/gemma3/phi3/mixtral/qwen3moe/resnet/vit/whisper/clip/unet/vae)
+ HF safetensors weight import. The reference delegates models to
transformers; here they ship in-tree (SURVEY hard-part #3: torch-free
model story)."""

from .bert import (
    BERT_SHARDING_RULES,
    BertConfig,
    BertForSequenceClassification,
    bert_classification_loss,
    create_bert_model,
)
from .gptneox import (
    GPTNEOX_SHARDING_RULES,
    GPTNeoXConfig,
    GPTNeoXModel,
    create_gptneox_model,
)
from .gpt2 import (
    GPT2_SHARDING_RULES,
    GPT2Config,
    GPT2Model,
    create_gpt2_model,
)
from .llama import (
    LLAMA_SHARDING_RULES,
    LlamaConfig,
    LlamaModel,
    causal_lm_loss,
    create_llama_model,
)
from .mistral import (
    MISTRAL_SHARDING_RULES,
    MistralConfig,
    MistralModel,
    create_mistral_model,
)
from .joyai_llm_flash import (
    JOYAI_SHARDING_RULES,
    JoyAIFlashConfig,
    JoyAIFlashModel,
    create_joyai_flash_model,
)
from .jamba import (
    JAMBA_SHARDING_RULES,
    JambaConfig,
    JambaModel,
    create_jamba_model,
)
from .lfm2_moe import (
    LFM2_MOE_SHARDING_RULES,
    Lfm2MoeConfig,
    Lfm2MoeModel,
    create_lfm2_moe_model,
)
from .granitemoehybrid import (
    GRANITE_MOE_HYBRID_SHARDING_RULES,
    GraniteMoeHybridConfig,
    GraniteMoeHybridModel,
    create_granitemoehybrid_model,
)
from .evabyte import (
    EVABYTE_SHARDING_RULES,
    EvaByteConfig,
    EvaByteModel,
    create_evabyte_model,
)
from .laguna import (
    LAGUNA_SHARDING_RULES,
    LagunaConfig,
    LagunaModel,
    create_laguna_model,
)
from .gemma import (
    GEMMA_SHARDING_RULES,
    GemmaConfig,
    GemmaModel,
    create_gemma_model,
)
from .phi3 import (
    PHI3_SHARDING_RULES,
    Phi3Config,
    Phi3Model,
    create_phi3_model,
)
from .qwen2 import (
    QWEN2_SHARDING_RULES,
    Qwen2Config,
    Qwen2Model,
    create_qwen2_model,
)
from .qwen3 import (
    QWEN3_SHARDING_RULES,
    Qwen3Config,
    Qwen3Model,
    create_qwen3_model,
)
from .olmo2 import (
    OLMO2_SHARDING_RULES,
    Olmo2Config,
    Olmo2Model,
    create_olmo2_model,
)
from .gemma2 import (
    GEMMA2_SHARDING_RULES,
    Gemma2Config,
    Gemma2Model,
    create_gemma2_model,
)
from .gemma3 import (
    GEMMA3_SHARDING_RULES,
    Gemma3Config,
    Gemma3Model,
    create_gemma3_model,
)
from .mixtral import (
    MIXTRAL_SHARDING_RULES,
    MixtralConfig,
    MixtralModel,
    create_mixtral_model,
    mixtral_lm_loss,
)
from .qwen3_moe import (
    QWEN3_MOE_SHARDING_RULES,
    Qwen3MoeConfig,
    Qwen3MoeModel,
    create_qwen3_moe_model,
    qwen3_moe_lm_loss,
)
from .resnet import (
    RESNET_SHARDING_RULES,
    ResNet,
    ResNetConfig,
    create_resnet_model,
    resnet_classification_loss,
)
from .t5 import (
    T5_SHARDING_RULES,
    T5Config,
    T5Model,
    create_t5_model,
    seq2seq_lm_loss,
)
from .vit import (
    VIT_SHARDING_RULES,
    ViT,
    ViTConfig,
    create_vit_model,
    vit_classification_loss,
)
from .clip import (
    CLIP_SHARDING_RULES,
    CLIPConfig,
    CLIPModel,
    clip_contrastive_loss,
    create_clip_model,
)
from .whisper import (
    WHISPER_SHARDING_RULES,
    WhisperConfig,
    WhisperModel,
    create_whisper_model,
)
from .unet import (
    UNET_SHARDING_RULES,
    UNet2D,
    UNetConfig,
    create_unet_model,
)
from .vae import (
    VAE_SHARDING_RULES,
    VAE,
    VAEConfig,
    create_vae_model,
    vae_loss,
)
from .hub import (  # noqa: E402 — HF safetensors importers
    load_hf_bert,
    load_hf_gemma,
    load_hf_gemma2,
    load_hf_gemma3,
    load_hf_gpt2,
    load_hf_gptneox,
    load_hf_llama,
    load_hf_mistral,
    load_hf_mixtral,
    load_hf_phi3,
    load_hf_olmo2,
    load_hf_qwen2,
    load_hf_qwen3,
    load_hf_qwen3_moe,
    load_hf_t5,
    load_hf_vit,
    load_hf_clip,
    load_hf_whisper,
    read_safetensors_state,
)
