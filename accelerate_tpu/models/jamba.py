"""Jamba (``model_type`` ``jamba``; AI21-Jamba2-3B / Jamba Reasoning 3B):
the llama core with state-space layers beside attention.

Of every ``attn_layer_period`` layers one attends (grouped-query, **no**
rotary or other position encoding: the family publishes none, positions
reach the model through the recurrence) and the others mix through a
Mamba-1 selective scan (``d_inner = mamba_expand * hidden_size``, a state
of ``[mamba_d_state, d_inner]`` a sequence, a causal depthwise convolution
of ``mamba_d_conv`` taps in front, RMSNorms on ``dt``, ``B`` and ``C``).
Every layer is pre-norm with a SwiGLU MLP; the head is the embedding. All
of that is :class:`~accelerate_tpu.models.llama.LlamaConfig` keys, so the
family reuses :mod:`accelerate_tpu.models.llama` wholesale, in the manner
of :mod:`accelerate_tpu.models.joyai_llm_flash`: the module, the decode
contract, the cache (paged K/V pools for the attention layers, one row of
recurrent state a slot for the others) and the serving engine are the core's.

Not here: the larger Jambas' ``num_experts`` 16. The core now builds routed
experts beside a stateful mixer (:mod:`accelerate_tpu.models.lfm2_moe`
runs them), by ``first_k_dense_replace``; this family chooses its expert
layers by ``expert_layer_period`` / ``expert_layer_offset`` and scores them
by softmax, which the core does not build, and this checkpoint publishes
``num_experts`` 1, so they select nothing: ``num_experts > 1`` is refused by
name (no configuration runs it).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from .llama import LLAMA_SHARDING_RULES, LlamaConfig, LlamaModel, create_llama_model

JAMBA_SHARDING_RULES = LLAMA_SHARDING_RULES
JambaModel = LlamaModel


@dataclasses.dataclass
class JambaConfig(LlamaConfig):
    """Llama config with the published ``config.json`` of
    ai21labs/AI21-Jamba2-3B as defaults."""

    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: Optional[float] = None  # the family has no position encoding
    tie_word_embeddings: bool = True
    attn_layer_period: Optional[int] = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_experts: int = 1
    num_experts_per_tok: int = 1
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    scan_layers: bool = False  # the core builds state-space layers unrolled only

    def __post_init__(self):
        if self.num_experts > 1:
            raise NotImplementedError(
                f"model_type jamba with num_experts={self.num_experts}: routed experts in a hybrid layer "
                "(expert_layer_period / expert_layer_offset) are not built; every layer's FFN is the dense MLP"
            )

    @classmethod
    def tiny(cls, **kw) -> "JambaConfig":
        """Every mechanism at toy widths: attention at layers 1 and 3 of 4, state-space layers at 0 and 2."""
        tiny = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=1, max_position_embeddings=128, attn_layer_period=2, attn_layer_offset=1,
            mamba_d_state=8, mamba_dt_rank=8,
        )
        return cls(**{**tiny, **kw})


def create_jamba_model(config: Optional[JambaConfig] = None, seed: int = 0, seq_len: int = 128, dtype=None):
    """A :class:`~accelerate_tpu.modeling.Model` running the llama module
    with state-space layers beside attention (all from the config's keys)."""
    return create_llama_model(config or JambaConfig.tiny(), seed=seed, seq_len=seq_len, dtype=dtype)
