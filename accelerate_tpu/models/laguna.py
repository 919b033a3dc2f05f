"""Laguna (``model_type`` ``laguna``; poolside/Laguna-XS.2, 33.4B-A3B): a llama
whose layers are of two kinds of attention that differ in more than the band,
with routed experts in every layer but the first.

* ``layer_types``: every fourth layer from 0 is ``full_attention``, the others
  ``sliding_attention`` under ``sliding_window`` 512 (query ``i`` sees keys
  ``i - 511 .. i``);
* ``num_attention_heads_per_layer``: 48 query heads on a full layer, 64 on a
  window layer, both on 8 key/value heads of 128 (groups of 6 and of 8);
* ``rope_parameters`` by layer type: a full layer turns the first half of each
  head (``partial_rotary_factor`` 0.5) under YaRN (``rope_theta`` 500,000,
  ``factor`` 64, ``original_max_position_embeddings`` 4096, ``beta_fast`` 64,
  ``beta_slow`` 1, cosines and sines times ``attention_factor``), a window layer
  the whole head under the plain rule at base 10,000;
* ``gating: true``: a gate a head on the attention output;
* ``mlp_layer_types``: layer 0 a SwiGLU of ``intermediate_size`` 8192, every
  other layer 256 routed SwiGLU experts of 512 with 8 a token, one shared expert
  of 512, ``moe_routed_scaling_factor`` 2.5 on the output.

All of that is :class:`~accelerate_tpu.models.llama.LlamaConfig` keys, so the
family reuses :mod:`accelerate_tpu.models.llama` wholesale; under the serving
engine's paged layout the window layers keep a pool and a table of their own,
used as a ring (``docs/usage_guides/serving.md``). The published names that
differ from the core's are fields here and ``__post_init__`` carries them over:
``num_experts`` (``n_routed_experts``), ``shared_expert_intermediate_size``
(``shared_intermediate_size``), ``moe_routed_scaling_factor``
(``routed_scaling_factor``), ``gating`` (``attn_gate``).

**Assumed** (the config names them and does not spell them out; the family's
convention, as Jamba's inner norms and LFM2's selection bias were): the gate is
``softplus`` of one scalar a head taken from the layer's normed input
(``g_proj``); queries and keys each go through an RMSNorm over the head's 128
values with a learned scale (``qk_norm``); the router scores by a sigmoid with a
selection bias that chooses and does not weigh, the chosen scores normalised
(``scoring_func`` ``sigmoid``, ``norm_topk_prob``: the convention the 2.5 comes
from). Departures: rotary turns adjacent pairs ``(2i, 2i + 1)`` as everywhere on
the core; an importer re-pairs the turned columns of ``q_proj`` / ``k_proj`` and
the norms' scales. No importer of checkpoints yet.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from .llama import LLAMA_SHARDING_RULES, LlamaConfig, LlamaModel, create_llama_model

LAGUNA_SHARDING_RULES = LLAMA_SHARDING_RULES
LagunaModel = LlamaModel

_PERIOD = ("full_attention", "sliding_attention", "sliding_attention", "sliding_attention")
LAGUNA_XS2_LAYER_TYPES = _PERIOD * 10
LAGUNA_XS2_ROPE_PARAMETERS = {
    "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
                       "beta_slow": 1, "beta_fast": 64, "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
}


@dataclasses.dataclass
class LagunaConfig(LlamaConfig):
    """Llama config with the published ``config.json`` of poolside/Laguna-XS.2 as defaults. The three lists a
    layer (``layer_types``, ``mlp_layer_types``, ``num_attention_heads_per_layer``) may be given whole: the first
    ``num_hidden_layers`` entries count (a pipeline stage's cut of the leading layers)."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: Optional[int] = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    sliding_window: Optional[int] = 512
    layer_types: Optional[tuple] = LAGUNA_XS2_LAYER_TYPES
    mlp_layer_types: Optional[tuple] = ("dense",) + ("sparse",) * 39
    num_attention_heads_per_layer: Optional[tuple] = tuple(48 if kind == "full_attention" else 64 for kind in LAGUNA_XS2_LAYER_TYPES)
    rope_parameters: Optional[dict] = dataclasses.field(default_factory=lambda: {k: dict(v) for k, v in LAGUNA_XS2_ROPE_PARAMETERS.items()})
    qk_norm: bool = True
    num_experts_per_tok: int = 8
    moe_intermediate_size: Optional[int] = 512
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    scan_layers: bool = False  # layers of two kinds: unrolled
    # the published names of keys the core has under another
    num_experts: int = 256
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    moe_apply_router_weight_on_input: bool = False
    gating: bool = True

    def __post_init__(self):
        if self.moe_apply_router_weight_on_input:
            raise NotImplementedError("the routed experts weigh their outputs: moe_apply_router_weight_on_input is false as published")
        n = self.num_hidden_layers
        self.layer_types = tuple(self.layer_types)[:n]
        self.mlp_layer_types = tuple(self.mlp_layer_types)[:n]
        self.num_attention_heads_per_layer = tuple(self.num_attention_heads_per_layer)[:n]
        if not len(self.layer_types) == len(self.mlp_layer_types) == len(self.num_attention_heads_per_layer) == n:
            raise ValueError(f"layer_types, mlp_layer_types and num_attention_heads_per_layer need {n} entries each")
        self.n_routed_experts = self.num_experts
        self.shared_intermediate_size = self.shared_expert_intermediate_size
        self.routed_scaling_factor = self.moe_routed_scaling_factor
        self.attn_gate = bool(self.gating)

    @classmethod
    def tiny(cls, **kw) -> "LagunaConfig":
        """Every mechanism at toy widths: five layers (``f s s s f``), 6 and 8 query heads on 2 key/value heads of
        16 (groups of 3 and 4), a window of 8, the first half of a full layer's head turned under YaRN, eight experts
        of 32 with two a token and a shared one."""
        rope = {k: dict(v) for k, v in LAGUNA_XS2_ROPE_PARAMETERS.items()}
        rope["full_attention"].update(factor=4, original_max_position_embeddings=32, beta_fast=8)
        tiny = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=5, num_attention_heads=6,
            num_key_value_heads=2, head_dim=16, max_position_embeddings=128, sliding_window=8,
            num_attention_heads_per_layer=tuple(6 if kind == "full_attention" else 8 for kind in LAGUNA_XS2_LAYER_TYPES),
            rope_parameters=rope, num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
            shared_expert_intermediate_size=32,
        )
        return cls(**{**tiny, **kw})


def create_laguna_model(config: Optional[LagunaConfig] = None, seed: int = 0, seq_len: int = 128, dtype=None):
    """A :class:`~accelerate_tpu.modeling.Model` running the llama module with full and window attention
    layers, a gate a head and routed experts (all from the config's keys)."""
    return create_llama_model(config or LagunaConfig.tiny(), seed=seed, seq_len=seq_len, dtype=dtype)
