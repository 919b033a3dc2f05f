"""Mistral: the llama architecture + sliding-window attention.

Mistral-7B is structurally llama (RMSNorm pre-norm, rotary, SwiGLU,
GQA) with one semantic change — every position attends to at most the
last ``sliding_window`` keys — plus different default widths (14336
intermediate, 8 KV heads; rope theta 1e4 for v0.1, 1e6 for v0.2/v0.3).
The family therefore reuses
:mod:`accelerate_tpu.models.llama` wholesale: :class:`MistralConfig`
subclasses :class:`LlamaConfig` (the ``sliding_window`` field lives
there so the band mask threads through the shared attention, KV-cache,
and paged-cache paths), and the module/sharding/loss/quantization
surfaces are the llama ones.

The reference has no in-tree models (it delegates to transformers,
SURVEY §2.2/hard-part #3); importer parity is tested against
``transformers.MistralForCausalLM`` in tests/test_hf_parity.py.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from .llama import (
    LLAMA_SHARDING_RULES,
    LlamaConfig,
    LlamaModel,
    create_llama_model,
)

MISTRAL_SHARDING_RULES = LLAMA_SHARDING_RULES
MistralModel = LlamaModel


@dataclasses.dataclass
class MistralConfig(LlamaConfig):
    """Llama config with Mistral-7B-v0.1 defaults: 32k context with a
    4096-token window, theta 1e4. v0.2/v0.3 dropped the window and
    raised theta — use :meth:`mistral_7b_v3` for those checkpoints (the
    wrong variant means wrong rotary angles or a spurious band mask)."""

    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 32768
    rope_theta: float = 10000.0
    sliding_window: Optional[int] = 4096

    @classmethod
    def tiny(cls, **kw) -> "MistralConfig":
        kw.setdefault("vocab_size", 256)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("num_hidden_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("num_key_value_heads", 2)
        kw.setdefault("max_position_embeddings", 128)
        kw.setdefault("sliding_window", 8)
        return cls(**kw)

    @classmethod
    def mistral_7b_v1(cls, **kw) -> "MistralConfig":
        """Mistral-7B-v0.1: theta 1e4, sliding window 4096."""
        return cls(**kw)

    @classmethod
    def mistral_7b_v3(cls, **kw) -> "MistralConfig":
        """Mistral-7B-v0.2/v0.3: theta 1e6, NO sliding window (the v0.2
        change); v0.3 only grew the vocab for tool tokens."""
        kw.setdefault("vocab_size", 32768)
        kw.setdefault("rope_theta", 1e6)
        kw.setdefault("sliding_window", None)
        return cls(**kw)


def create_mistral_model(
    config: Optional[MistralConfig] = None, seed: int = 0, seq_len: int = 128, dtype=None
):
    """A :class:`~accelerate_tpu.modeling.Model` running the llama module
    with the Mistral band mask (config.sliding_window)."""
    return create_llama_model(config or MistralConfig.tiny(), seed=seed, seq_len=seq_len, dtype=dtype)
