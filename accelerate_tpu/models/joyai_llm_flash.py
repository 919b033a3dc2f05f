"""JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``, 48B-A2.7B): the llama
core with multi-head latent attention and routed experts.

Structurally a DeepSeek-V3-style decoder: every layer attends through one
compressed row a token (``kv_lora_rank`` 512 + a shared rotary key of 64;
queries through a rank-1536 bottleneck), the first layer keeps a dense
SwiGLU MLP, and every later layer routes each token to 8 of 256 experts of
width 768 (sigmoid scores, a selection bias that chooses and does not
weigh, normalised weights times 2.5, no capacity) plus one shared expert.
All of that is :class:`~accelerate_tpu.models.llama.LlamaConfig` keys, so
the family reuses :mod:`accelerate_tpu.models.llama` wholesale, in the
manner of :mod:`accelerate_tpu.models.mistral`: the module, the decode
contract, the paged latent cache and the serving engine are the core's.

Not here: the multi-token-prediction module (``num_nextn_predict_layers``
1), a training objective and an optional self-draft that the published
serving code leaves out as well.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from .llama import LLAMA_SHARDING_RULES, LlamaConfig, LlamaModel, create_llama_model

JOYAI_SHARDING_RULES = LLAMA_SHARDING_RULES
JoyAIFlashModel = LlamaModel


@dataclasses.dataclass
class JoyAIFlashConfig(LlamaConfig):
    """Llama config with the published ``config.json`` of
    jdopensource/JoyAI-LLM-Flash as defaults. ``num_key_value_heads`` and
    ``head_dim`` are published (32, 64) and unused by latent attention."""

    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 32000000.0
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: Optional[int] = 512
    qk_nope_head_dim: Optional[int] = 128
    qk_rope_head_dim: Optional[int] = 64
    v_head_dim: Optional[int] = 128
    n_routed_experts: Optional[int] = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: Optional[int] = 768
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    scan_layers: bool = False  # the core builds latent attention and routed experts unrolled only

    @classmethod
    def tiny(cls, **kw) -> "JoyAIFlashConfig":
        """Every mechanism at toy widths: 1 dense + 2 expert layers, 8 experts, 2 a token, 1 shared."""
        tiny = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=128, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32,
        )
        return cls(**{**tiny, **kw})


def create_joyai_flash_model(
    config: Optional[JoyAIFlashConfig] = None, seed: int = 0, seq_len: int = 128, dtype=None
):
    """A :class:`~accelerate_tpu.modeling.Model` running the llama module
    with latent attention and routed experts (all from the config's keys)."""
    return create_llama_model(config or JoyAIFlashConfig.tiny(), seed=seed, seq_len=seq_len, dtype=dtype)
