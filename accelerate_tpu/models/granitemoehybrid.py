"""Granite 4.0-H (``model_type`` ``granitemoehybrid``; ibm-granite/granite-4.0-h-small,
32B-A9B): the llama core with Mamba-2 layers beside attention, routed experts
with a shared one in every layer, and Granite's four multipliers.

``layer_types`` names each layer's mixer. A ``"mamba"`` layer mixes through
:class:`~accelerate_tpu.models.llama.Mamba2Mixer` (``mamba_n_heads`` heads of
``mamba_d_head`` channels, a state of ``[mamba_d_state, heads * d_head]`` a
sequence, one decay a head a token, a gated RMSNorm before ``out_proj``); an
``"attention"`` layer is grouped-query attention with **no** position
encoding (``position_embedding_type`` ``"nope"``: positions reach the model
through the recurrence, as in :mod:`accelerate_tpu.models.jamba`) and the
score scale ``attention_multiplier`` itself. Every layer's feed-forward
routes ``num_experts_per_tok`` of ``num_local_experts`` SwiGLU experts of
``intermediate_size`` a token by its largest raw router logits, weighted by
a softmax over those logits alone, and adds one shared SwiGLU of
``shared_intermediate_size``. ``embedding_multiplier`` scales the
embeddings, ``residual_multiplier`` both branches of every layer,
``logits_scaling`` divides the logits; the head is the embedding. All of
that is :class:`~accelerate_tpu.models.llama.LlamaConfig` keys, so the family
reuses :mod:`accelerate_tpu.models.llama` wholesale: the module, the decode
contract, the cache (a paged K/V pool for the attention layers, one row of
recurrent state a slot for the others) and the serving engine are the core's.

The published names that differ from the core's are fields here and
``__post_init__`` carries them over: ``num_local_experts``
(``n_routed_experts``: the router's columns), ``intermediate_size`` (one
expert's width, ``moe_intermediate_size``), ``position_embedding_type``
(``"nope"`` sets ``rope_theta`` None).

**One chip's share** (``expert_shares``, ``expert_share``; not published
keys): under expert parallelism over ``expert_shares`` chips a layer here
holds ``num_local_experts / expert_shares`` experts' matrices, routes over
all ``num_local_experts`` columns and returns its own experts' part of the
routed sum plus the shared expert. The exchange between the shares is not
built, and nothing stands in for it: one share alone computes a partial
layer, which is what a benchmark cell cut to a chip's share measures.

Departures: the published expert input is one fused ``input_linear``
``[E, 2 x width, hidden]``; here ``gate_proj`` and ``up_proj`` are two
stacked tensors (an importer splits it). The recurrent state is float32 (the
repo's convention for a recurrent state; the published code keeps the
model's type). No importer of checkpoints yet.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from .llama import LLAMA_SHARDING_RULES, LlamaConfig, LlamaModel, create_llama_model

GRANITE_MOE_HYBRID_SHARDING_RULES = LLAMA_SHARDING_RULES
GraniteMoeHybridModel = LlamaModel

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
GRANITE_4_0_H_SMALL_LAYER_TYPES = _PERIOD * 4


@dataclasses.dataclass
class GraniteMoeHybridConfig(LlamaConfig):
    """Llama config with the published ``config.json`` of
    ibm-granite/granite-4.0-h-small as defaults."""

    vocab_size: int = 100352
    hidden_size: int = 4096
    intermediate_size: int = 768  # one routed expert's width
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    layer_types: Optional[tuple] = GRANITE_4_0_H_SMALL_LAYER_TYPES
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_heads: Optional[int] = 128
    mamba_d_head: Optional[int] = 64
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_experts_per_tok: int = 10
    shared_intermediate_size: Optional[int] = 1536
    scoring_func: str = "softmax_topk"
    embedding_multiplier: Optional[float] = 12.0
    residual_multiplier: Optional[float] = 0.22
    attention_multiplier: Optional[float] = 0.0078125
    logits_scaling: Optional[float] = 16.0
    scan_layers: bool = False  # the core builds layers named in ``layer_types`` unrolled only
    # the published names of keys the core has under another
    num_local_experts: int = 72
    position_embedding_type: str = "nope"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        self.n_routed_experts = self.num_local_experts
        self.moe_intermediate_size = self.intermediate_size
        if self.position_embedding_type == "nope":
            self.rope_theta = None
        elif self.position_embedding_type != "rope":
            raise NotImplementedError(f"position_embedding_type {self.position_embedding_type!r}: nope and rope are built")

    @classmethod
    def tiny(cls, **kw) -> "GraniteMoeHybridConfig":
        """Every mechanism at toy widths: attention at layer 2 of 4, Mamba-2 layers of 4 heads of 8 around
        it, 8 routed experts of 4 a token with a shared one of its own width in every layer."""
        tiny = dict(
            vocab_size=256, hidden_size=16, intermediate_size=8, num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128, layer_types=("mamba", "mamba", "attention", "mamba"),
            mamba_d_state=16, mamba_n_heads=4, mamba_d_head=8, mamba_chunk_size=8, num_local_experts=8,
            num_experts_per_tok=4, shared_intermediate_size=24, attention_multiplier=0.25,
        )
        return cls(**{**tiny, **kw})


def create_granitemoehybrid_model(config: Optional[GraniteMoeHybridConfig] = None, seed: int = 0, seq_len: int = 128, dtype=None):
    """A :class:`~accelerate_tpu.modeling.Model` running the llama module
    with Mamba-2 layers beside attention, routed experts by a softmax over
    the top k and Granite's multipliers (all from the config's keys)."""
    return create_llama_model(config or GraniteMoeHybridConfig.tiny(), seed=seed, seq_len=seq_len, dtype=dtype)
