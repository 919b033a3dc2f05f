"""EvaByte (``model_type`` ``evabyte``; EvaByte/EvaByte, 6.5 B): a byte-level
llama (vocabulary 320, no tokenizer) whose every attention layer is EVA,
chunked linearised attention.

Position ``t`` reads the exact keys and values of its own aligned window
of ``window_size`` (2048) bytes and, for every chunk of ``chunk_size`` (16)
bytes of the windows before it, ONE pooled key and ONE pooled value, all
under one softmax (:mod:`accelerate_tpu.ops.eva_attention`: the equations,
the pooling under ``adaptive_mu_k`` / ``adaptive_phi``, the three paths).
So a sequence's cache is one window of rows and a sixteenth of a row for
everything older, and the window is aligned, not sliding: it closes at
once. Around it: as many key/value heads as query heads, rotary over the
whole head (``rope_theta`` 100,000), SwiGLU, RMSNorm with scale ``1 + w``
(``norm_add_unit_offset``), residual sums in float32 (``fp32_skip_add``),
float32 logits (``fp32_logits``: the core's head is float32 already), an
untied head. All of that is :class:`~accelerate_tpu.models.llama.LlamaConfig`
keys, so the family reuses :mod:`accelerate_tpu.models.llama` wholesale,
in the manner of :mod:`accelerate_tpu.models.jamba`; under the serving
engine's paged layout the summaries live in pages of the layer's own pool
under a second table (``docs/usage_guides/serving.md``).

The published names that differ from the core's are fields here and
``__post_init__`` carries them over: ``window_size`` (``eva_window_size``),
``chunk_size`` (``eva_chunk_size``), ``norm_add_unit_offset``
(``norm_plus_one``).

Not built: the seven further prediction heads (``num_pred_heads`` 8: head
``i`` predicts byte ``t + 1 + i``; the head held is the first, ``[hidden,
vocab_size]``) and the self-speculative multi-byte decoding they exist for.
Departures: rotary turns adjacent pairs ``(2i, 2i + 1)`` as everywhere on
the core, where the published code turns halves: an importer re-pairs the
columns of ``q_proj`` / ``k_proj`` (:mod:`accelerate_tpu.models.hub`) and of
``adaptive_mu_k``, which meets the rotated keys. No importer of checkpoints yet.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from .llama import LLAMA_SHARDING_RULES, LlamaConfig, LlamaModel, create_llama_model

EVABYTE_SHARDING_RULES = LLAMA_SHARDING_RULES
EvaByteModel = LlamaModel


@dataclasses.dataclass
class EvaByteConfig(LlamaConfig):
    """Llama config with the published ``config.json`` of EvaByte/EvaByte as defaults."""

    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-5
    rope_theta: Optional[float] = 100000.0
    tie_word_embeddings: bool = False
    attention_class: Optional[str] = "eva"
    fp32_skip_add: bool = True
    scan_layers: bool = False  # the core builds EVA layers unrolled only: a pool and two tables a layer
    # the published names of keys the core has under another
    window_size: int = 2048
    chunk_size: int = 16
    norm_add_unit_offset: bool = True
    fp32_logits: bool = True  # the core's head computes in float32 whatever this says
    num_pred_heads: int = 8  # published; one head is built

    def __post_init__(self):
        self.eva_window_size, self.eva_chunk_size = self.window_size, self.chunk_size
        self.norm_plus_one = self.norm_add_unit_offset

    @classmethod
    def tiny(cls, **kw) -> "EvaByteConfig":
        """Every mechanism at toy widths: windows of 32 positions in chunks of 4, so a sequence of a
        hundred bytes closes three windows."""
        tiny = dict(
            vocab_size=320, hidden_size=64, intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=128, window_size=32, chunk_size=4,
        )
        return cls(**{**tiny, **kw})


def create_evabyte_model(config: Optional[EvaByteConfig] = None, seed: int = 0, seq_len: int = 128, dtype=None):
    """A :class:`~accelerate_tpu.modeling.Model` running the llama module
    with EVA attention in every layer (all from the config's keys)."""
    return create_llama_model(config or EvaByteConfig.tiny(), seed=seed, seq_len=seq_len, dtype=dtype)
