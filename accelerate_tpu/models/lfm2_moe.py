"""LFM2-MoE (``model_type`` ``lfm2_moe``; LiquidAI/LFM2-8B-A1B): the llama
core with gated short convolutions beside attention and routed experts in
every layer past the leading dense ones.

``layer_types`` names each layer's operator. A ``"conv"`` layer mixes
through :class:`~accelerate_tpu.models.llama.ShortConvMixer` (``[B, C, x] =
in_proj(u)``, ``C * conv1d(B * x)`` over ``conv_L_cache`` taps, ``out_proj``;
its state a sequence is the last ``conv_L_cache - 1`` rows of ``B * x``); a
``"full_attention"`` layer is grouped-query attention with an RMSNorm over
each head of q and k before rotary. The first ``num_dense_layers`` layers
keep a SwiGLU of ``intermediate_size``; every later one routes
``num_experts_per_tok`` of ``num_experts`` SwiGLU experts of
``moe_intermediate_size`` a token by sigmoid scores, with a selection bias
(``use_expert_bias``) that chooses and does not weigh, normalised weights
and no shared expert. Pre-norm residuals, a final norm, the head tied to
the embedding. All of that is
:class:`~accelerate_tpu.models.llama.LlamaConfig` keys, so the family
reuses :mod:`accelerate_tpu.models.llama` wholesale, in the manner of
:mod:`accelerate_tpu.models.jamba`: the module, the decode contract, the
cache (paged K/V pools for the attention layers, one ``conv_state`` row a
slot for the others) and the serving engine are the core's.

The published names that differ from the core's are fields here and
``__post_init__`` carries them over: ``norm_eps`` (``rms_norm_eps``),
``num_experts`` (``n_routed_experts``), ``num_dense_layers``
(``first_k_dense_replace``).

Departures: the published normaliser of the chosen scores is ``sum + 1e-6``
and :func:`~accelerate_tpu.ops.moe.sigmoid_topk_routing` adds ``1e-20`` (5e-7
of a weight, under bfloat16's step); rotary turns adjacent pairs ``(2i, 2i +
1)`` as everywhere on the core, where the published code turns halves: an
importer re-pairs the columns of ``q_proj`` / ``k_proj`` and the norms'
scales (:mod:`accelerate_tpu.models.hub`). No importer of checkpoints yet.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from .llama import LLAMA_SHARDING_RULES, LlamaConfig, LlamaModel, create_llama_model

LFM2_MOE_SHARDING_RULES = LLAMA_SHARDING_RULES
Lfm2MoeModel = LlamaModel

_PERIOD = ("conv", "conv", "full_attention", "conv")
LFM2_8B_A1B_LAYER_TYPES = _PERIOD * 4 + ("conv", "conv", "full_attention", "conv", "conv", "full_attention", "conv", "conv")


@dataclasses.dataclass
class Lfm2MoeConfig(LlamaConfig):
    """Llama config with the published ``config.json`` of
    LiquidAI/LFM2-8B-A1B as defaults."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    num_hidden_layers: int = 24
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 128000
    rope_theta: Optional[float] = 1000000.0
    qk_norm: bool = True
    tie_word_embeddings: bool = True
    layer_types: Optional[tuple] = LFM2_8B_A1B_LAYER_TYPES
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts_per_tok: int = 4
    moe_intermediate_size: Optional[int] = 1792
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scan_layers: bool = False  # the core builds layers named in ``layer_types`` unrolled only
    # the published names of keys the core has under another
    norm_eps: float = 1e-5
    num_experts: int = 32
    num_dense_layers: int = 2
    use_expert_bias: bool = True  # False: the bias stays at its zero initialisation and chooses nothing

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        self.rms_norm_eps = self.norm_eps
        self.n_routed_experts = self.num_experts
        self.first_k_dense_replace = self.num_dense_layers

    @classmethod
    def tiny(cls, **kw) -> "Lfm2MoeConfig":
        """Every mechanism at toy widths: two leading dense layers, both operators (attention at 2 and 4
        of 6), routed experts beside each, 8 experts of 4 a token."""
        tiny = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=6, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128, moe_intermediate_size=32, num_experts=8,
            layer_types=("conv", "conv", "full_attention", "conv", "full_attention", "conv"),
        )
        return cls(**{**tiny, **kw})


def create_lfm2_moe_model(config: Optional[Lfm2MoeConfig] = None, seed: int = 0, seq_len: int = 128, dtype=None):
    """A :class:`~accelerate_tpu.modeling.Model` running the llama module
    with gated short convolutions beside attention and routed experts (all
    from the config's keys)."""
    return create_llama_model(config or Lfm2MoeConfig.tiny(), seed=seed, seq_len=seq_len, dtype=dtype)
