"""Llama-family decoder (flax.linen): RMSNorm, RoPE, GQA, SwiGLU.

The scale-out model for the framework (the reference's FSDP2 benchmark
fine-tunes Llama-2-7B — BASELINE.json configs). TPU-first choices:

* sharding rules for the full 4D layout (fsdp x tensor x seq x data):
  Megatron column/row splits over ``tensor``, sequence-dim activation
  sharding constraint over ``seq`` (Megatron-SP equivalent);
* ``lax.scan`` over layers (``scan_layers=True``) so trace/compile time is
  O(1) in depth — the TPU answer to the reference's "regional compilation"
  (reference: utils/other.py:101-172 compile_regions, SURVEY §2.6);
* attention dispatches to flash/blockwise for long sequences.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.fp8 import policy_dot_general as _pdg
from jax.sharding import PartitionSpec as P

from ..modeling import Model


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    # None: the family publishes no rotary (or other) position encoding, and
    # attention applies none (Jamba: positions reach the model through its
    # state-space layers)
    rope_theta: Optional[float] = 10000.0
    # HF-style rope_scaling dict ({"rope_type": "llama3"|"linear"|"yarn"|
    # "longrope", "factor": ..., ...}); Llama-3.1/3.2 checkpoints require
    # the llama3 rescale
    rope_scaling: Optional[dict] = None
    # HF keeps this at the config top level for Phi-3 longrope checkpoints;
    # mirrors config.json's original_max_position_embeddings
    original_max_position_embeddings: Optional[int] = None
    scan_layers: bool = True
    remat: bool = True
    # "auto": ring attention when the mesh seq axis is non-trivial, else
    # dense/flash; "ring" | "all_to_all" | "dense" force a path.
    attention_impl: str = "auto"
    # Mistral-style sliding-window attention: each position attends to at
    # most the last `sliding_window` keys (itself included). None = full
    # causal. Short sequences mask the band in XLA; flash-length TPU
    # sequences run the banded flash kernel (O(S*W)); seq-sharded meshes
    # apply the band inside ring / all-to-all context parallelism.
    sliding_window: Optional[int] = None
    # Qwen2-style bias on the q/k/v projections only (o_proj stays
    # bias-free); importer re-pairs q/k biases for the rope convention
    qkv_bias: bool = False
    # Qwen3-style per-head RMSNorm on q and k (one [head_dim] scale
    # shared across heads, applied after the projection, before rope);
    # the importer re-pairs the scales for the interleaved rope layout
    qk_norm: bool = False
    # OLMo2-style FULL-WIDTH RMSNorm on the flat q/k projections
    # ([H*head_dim] / [H_kv*head_dim] scales, applied before the head
    # reshape); mutually exclusive with qk_norm
    qk_norm_flat: bool = False
    # OLMo2-style post-norms: normalize each sublayer's output before the
    # residual add instead of its input (no input_norm params)
    norm_after: bool = False
    # Gemma2-style sandwich norms: BOTH a pre- and post-norm around each
    # sublayer (input_norm/post_attn_norm around attention,
    # pre_ffn_norm/post_ffn_norm around the MLP)
    sandwich_norm: bool = False
    # Gemma2 logit softcapping: tanh-bound attention scores / final logits
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    # Gemma2: attention scale = query_pre_attn_scalar**-0.5 when set
    # (instead of head_dim**-0.5)
    query_pre_attn_scalar: Optional[float] = None
    # per-layer kind as checkpoints publish it: "sliding_attention" |
    # "full_attention" for Gemma2's alternating local/global layers, and
    # "conv" for a layer whose mixer is a :class:`ShortConvMixer` (LFM2) —
    # requires scan_layers=False (a scanned block shares one static config
    # across layers)
    layer_types: Optional[tuple] = None
    # Gemma3: sliding layers rotate with THIS theta (10k) and no rope
    # scaling, while full layers use rope_theta (1M) + rope_scaling
    rope_local_theta: Optional[float] = None
    # Gemma-family knobs: an explicit per-head width (None = hidden/heads),
    # the MLP gate activation, RMSNorm's (1 + scale) variant, and the
    # sqrt(hidden) embedding multiplier
    head_dim: Optional[int] = None
    mlp_activation: str = "silu"  # silu | gelu_tanh
    norm_plus_one: bool = False
    scale_embeddings: bool = False
    # share the embedding table with the LM head (Gemma always; small
    # Qwen2 variants): no separate lm_head param exists, so fine-tuning
    # cannot drift the two apart and the 256k-vocab table isn't duplicated
    tie_word_embeddings: bool = False
    # weight-only quantized block projections (int8|int4|nf4): every
    # q/k/v/o/gate/up/down kernel becomes a QuantDense whose packed codes
    # are the params — the decode-bandwidth win (set via
    # ``load_and_quantize_model``, not by hand)
    quant_method: Optional[str] = None
    quant_group_size: Optional[int] = None
    # Multi-head latent attention (DeepSeek-V2/V3-style checkpoints publish
    # these keys): with ``kv_lora_rank`` set, every layer's attention is
    # :class:`LatentAttention` and the cache holds one row of
    # ``kv_lora_rank + qk_rope_head_dim`` values a token, shared by all
    # heads. Layers are then unrolled (``scan_layers=False``).
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    # Routed experts (same checkpoints): with ``n_routed_experts`` set, the
    # layers after the first ``first_k_dense_replace`` replace the MLP by
    # :class:`RoutedFFN`: ``num_experts_per_tok`` SwiGLU experts of width
    # ``moe_intermediate_size`` a token, chosen without capacity (no token
    # is dropped), plus ``n_shared_experts`` that every token takes.
    # Leading dense layers keep ``intermediate_size``.
    n_routed_experts: Optional[int] = None
    num_experts_per_tok: int = 8
    moe_intermediate_size: Optional[int] = None
    n_shared_experts: int = 0
    first_k_dense_replace: int = 0
    routed_scaling_factor: float = 1.0
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    # State-space layers beside attention (Jamba-style checkpoints publish
    # these keys): with ``attn_layer_period`` set, layer ``i`` attends where
    # ``i % attn_layer_period == attn_layer_offset`` and every other layer's
    # mixer is a :class:`MambaMixer` (Mamba-1: ``d_inner = mamba_expand *
    # hidden_size``, a state of ``[mamba_d_state, d_inner]`` and the last
    # ``mamba_d_conv - 1`` inputs of its convolution a sequence, whatever
    # the length). Layers are then unrolled (``scan_layers=False``).
    attn_layer_period: Optional[int] = None
    attn_layer_offset: int = 0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None  # None: ceil(hidden_size / 16)
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # Gated short convolutions beside attention (LFM2-style checkpoints
    # publish these keys and name the layers in ``layer_types``): a "conv"
    # layer's mixer is a :class:`ShortConvMixer` of ``conv_L_cache`` taps,
    # whose state a sequence is its last ``conv_L_cache - 1`` inputs,
    # whatever the length. Layers are then unrolled (``scan_layers=False``).
    conv_L_cache: int = 3
    conv_bias: bool = False
    # Mamba-2 layers beside attention (Granite-4.0-H-style checkpoints publish these keys and name the
    # layers "mamba" / "attention" in ``layer_types``): with ``mamba_n_heads`` set, a "mamba" layer's mixer
    # is a :class:`Mamba2Mixer` of ``mamba_n_heads`` heads of ``mamba_d_head`` channels (``mamba_expand *
    # hidden_size`` together), ``mamba_n_groups`` groups of ``B`` / ``C`` (one is built), a state of
    # ``[mamba_d_state, mamba_n_heads * mamba_d_head]`` a sequence, chunks of ``mamba_chunk_size`` tokens
    # in a prefill. Layers are then unrolled (``scan_layers=False``).
    mamba_n_heads: Optional[int] = None
    mamba_d_head: Optional[int] = None
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    # Granite's four multipliers (None: the model has none, and its program is what it was): on the
    # embeddings, on both branches of every layer before the residual add, the attention score scale in
    # place of ``head_dim ** -0.5``, and a divisor of the logits
    embedding_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    attention_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None
    # a shared expert of its own width (None: ``moe_intermediate_size * n_shared_experts``)
    shared_intermediate_size: Optional[int] = None
    # Expert parallelism, one chip's share: the routed layers hold share ``expert_share`` of
    # ``expert_shares`` equal shares of the ``n_routed_experts`` experts (experts ``share * E / shares`` and
    # up), the router keeps all ``n_routed_experts`` columns, and a layer returns its held experts' part
    # of the routed sum (plus the shared expert, which every share computes alike). No exchange is built.
    expert_shares: int = 1
    expert_share: int = 0
    # EVA, chunked linearised attention (EvaByte-style checkpoints publish ``attention_class: "eva"``,
    # ``window_size`` and ``chunk_size``): with ``attention_class == "eva"`` every attention layer reads the
    # exact rows of its own aligned window of ``eva_window_size`` positions and one pooled key and value for
    # every chunk of ``eva_chunk_size`` positions of the windows before it (:mod:`..ops.eva_attention`), pooled
    # under two learned vectors a head (``adaptive_mu_k``, ``adaptive_phi``). Layers are then unrolled
    # (``scan_layers=False``). None: the model has no such layer, and its program is what it was.
    attention_class: Optional[str] = None
    eva_window_size: int = 2048
    eva_chunk_size: int = 16
    # residual sums taken in float32 and rounded once to the stream's type (EvaByte's ``fp32_skip_add``)
    fp32_skip_add: bool = False
    # Layers of two kinds of attention that differ in more than the band (Laguna-style checkpoints publish these
    # keys beside ``layer_types``; unrolled layers): query heads by layer (``num_attention_heads_per_layer``, on
    # the same ``num_key_value_heads``; ``head_dim`` is then stated), a rotary rule by layer TYPE
    # (``rope_parameters[layer_types[i]]``: ``rope_theta``, ``partial_rotary_factor`` and, with a ``rope_type``
    # other than ``default``, the ``rope_scaling`` dict itself), a gate a head on the attention output
    # (``attn_gate``: ``softplus(g_proj(x))``, one scalar a head, from the layer's normed input), and the
    # feed-forward by layer (``mlp_layer_types``: ``"dense"`` | ``"sparse"``, in place of
    # ``first_k_dense_replace``). None / False: the model has none of it, and its program is what it was.
    num_attention_heads_per_layer: Optional[tuple] = None
    rope_parameters: Optional[dict] = None
    partial_rotary_factor: float = 1.0  # the share of a head's values that rotary turns, its first ones
    attn_gate: bool = False
    mlp_layer_types: Optional[tuple] = None

    def mixer_kind(self, i: int) -> str:
        """The mixer of layer ``i``: ``"conv"`` or ``"mamba"`` where ``layer_types`` says so (a ``"mamba"``
        layer is ``"mamba2"`` in a config with ``mamba_n_heads``), ``"mamba"`` off the attention period of
        a config with one, else ``"attention"`` (latent where ``kv_lora_rank`` is set)."""
        if self.layer_types is not None and self.layer_types[i] == "conv":
            return "conv"
        if self.layer_types is not None and self.layer_types[i] == "mamba":
            return "mamba" if self.mamba_n_heads is None else "mamba2"
        if self.attn_layer_period is not None and i % self.attn_layer_period != self.attn_layer_offset:
            return "mamba"
        return "attention"

    def layer_overrides(self, i: int) -> dict:
        """What layer ``i`` overrides of this configuration by its place: its query heads
        (``num_attention_heads_per_layer``) and its type's rotary rule (``rope_parameters``)."""
        out = {}
        if self.num_attention_heads_per_layer is not None:
            out["num_attention_heads"] = self.num_attention_heads_per_layer[i]
        rule = (self.rope_parameters or {}).get(self.layer_types[i])
        if rule is not None:
            out["rope_theta"] = float(rule["rope_theta"])
            out["partial_rotary_factor"] = float(rule.get("partial_rotary_factor", 1.0))
            out["rope_scaling"] = None if rule.get("rope_type", "default") == "default" else dict(rule)
        return out

    @property
    def stateful(self) -> bool:
        """Some layer's mixer keeps a recurrent state a sequence (:data:`ops.paged_kv.STATE_LEAVES`)."""
        return self.attn_layer_period is not None or (
            self.layer_types is not None and any(kind in ("conv", "mamba") for kind in self.layer_types))

    @property
    def held_experts(self) -> tuple:
        """``(first, count)``: the routed experts this share holds, among the router's ``n_routed_experts``."""
        if self.n_routed_experts % self.expert_shares or not 0 <= self.expert_share < self.expert_shares:
            raise ValueError(
                f"share {self.expert_share} of {self.expert_shares} equal shares of {self.n_routed_experts} routed experts")
        count = self.n_routed_experts // self.expert_shares
        return self.expert_share * count, count

    @classmethod
    def llama2_7b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        kw.setdefault("vocab_size", 256)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("num_hidden_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("num_key_value_heads", 2)
        kw.setdefault("max_position_embeddings", 128)
        return cls(**kw)


# Megatron column/row splits over ``tensor``. Two path layouts exist:
# scan_layers=True stacks per-layer weights with a leading layer dim under
# ``layers/block/...`` (specs start with None for the scan dim);
# scan_layers=False names layers ``layer_<i>/...``. Anchored so neither
# rule set can match the other layout's paths.
LLAMA_SHARDING_RULES = [
    (r"embed_tokens/embedding", P("tensor", None)),
    # stacked (scan) variants: [L, in, out]-shaped kernels
    (r"layers/block/attn/(q|k|v)_proj/kernel", P(None, None, "tensor")),
    (r"layers/block/attn/(q|k|v)_proj/bias", P(None, "tensor")),
    (r"layers/block/attn/o_proj/kernel", P(None, "tensor", None)),
    (r"layers/block/mlp/(gate|up)_proj/kernel", P(None, None, "tensor")),
    (r"layers/block/mlp/down_proj/kernel", P(None, "tensor", None)),
    (r"lm_head/kernel", P(None, "tensor")),
    # unstacked variants (scan_layers=False): [in, out]-shaped kernels
    (r"layer_\d+/attn/(q|k|v)_proj/kernel", P(None, "tensor")),
    (r"layer_\d+/attn/(q|k|v)_proj/bias", P("tensor")),
    (r"layer_\d+/attn/o_proj/kernel", P("tensor", None)),
    (r"layer_\d+/mlp/(gate|up)_proj/kernel", P(None, "tensor")),
    (r"layer_\d+/mlp/down_proj/kernel", P("tensor", None)),
]

# Quantized variants: qdata/qscale are [*, n_groups, g(, packed), out] with
# a leading layer dim when stacked — column-parallel splits the trailing
# out dim; row-parallel splits the group dim of qdata and replicates the
# scales (the per-channel scale commutes with the contraction psum).
LLAMA_SHARDING_RULES += [
    (r"layers/block/(attn/(q|k|v)_proj|mlp/(gate|up)_proj)/(qdata|qscale)", P(None, None, None, "tensor")),
    (r"layers/block/(attn/o_proj|mlp/down_proj)/qdata", P(None, None, "tensor", None)),
    (r"layers/block/(attn/o_proj|mlp/down_proj)/qscale", P(None, None, None, None)),
    (r"layer_\d+/(attn/(q|k|v)_proj|mlp/(gate|up)_proj)/(qdata|qscale)", P(None, None, "tensor")),
    (r"layer_\d+/(attn/o_proj|mlp/down_proj)/qdata", P(None, "tensor", None)),
    (r"layer_\d+/(attn/o_proj|mlp/down_proj)/qscale", P(None, None, None)),
]

# Activation sharding (Megatron-SP equivalent): token dim over ``seq``.
ACTIVATION_SPEC = P(("data", "fsdp"), "seq", None)


def _dense(cfg: "LlamaConfig", features: int, name: str, dtype, use_bias: bool = False):
    """Block projection factory: plain Dense, QuantDense when the config
    carries a weight-only quantization method, or FP8Dense when the active
    precision policy requests the delayed-scaling fp8 recipe (amax
    histories in the ``fp8`` collection -> ``model.state``)."""
    if cfg.quant_method is not None:
        from ..ops.qdense import QuantDense

        return QuantDense(
            features, method=cfg.quant_method, group_size=cfg.quant_group_size, dtype=dtype,
            name=name, use_bias=use_bias,
        )
    from ..ops.fp8 import FP8Dense, fp8_recipe

    recipe = fp8_recipe()
    if recipe is not None and recipe.delayed_scaling:
        if use_bias:
            raise NotImplementedError("FP8Dense (delayed scaling) has no bias; qkv_bias models need the bf16 path")
        return FP8Dense(
            features,
            name=name,
            dtype=dtype,
            amax_history_len=recipe.amax_history_len,
            amax_compute_algo=recipe.amax_compute_algo,
            margin=recipe.margin,
        )
    return nn.Dense(features, use_bias=use_bias, name=name, dtype=dtype, dot_general=_pdg())


class RMSNorm(nn.Module):
    eps: float = 1e-5
    # Gemma convention: zero-initialised param applied as (1 + scale) —
    # checkpoints store the OFFSET, so the importer maps weights verbatim
    plus_one: bool = False

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.plus_one else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        if self.plus_one:
            # Gemma keeps normalize AND (1 + scale) in fp32, casting only
            # the result — matching HF's rounding so bf16 runs agree
            out = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps) * (1.0 + scale.astype(jnp.float32))
            return out.astype(x.dtype)
        # llama convention: cast the normalized stream first, multiply in
        # the stream dtype (HF LlamaRMSNorm order)
        normed = (x * jax.lax.rsqrt(var + self.eps)).astype(x.dtype)
        return normed * scale.astype(x.dtype)


def rope_frequencies(
    d: int,
    theta: float,
    scaling: Optional[dict] = None,
    *,
    max_pos: Optional[int] = None,
    seq_len: Optional[int] = None,
    orig_max: Optional[int] = None,
) -> tuple[jax.Array, float]:
    """``(inverse frequencies, attention factor)`` for rotary embedding,
    with HF-style ``rope_scaling`` applied (reference behavior: the
    reference delegates models to ``transformers``, whose
    ``ROPE_INIT_FUNCTIONS`` implement these; Llama-3.1/3.2 checkpoints
    REQUIRE the ``llama3`` rescale or every rotary angle is wrong at every
    position). The attention factor multiplies cos/sin (1.0 except
    yarn/longrope).

    Supported ``rope_type``s: ``default``; ``linear`` (position
    interpolation: all frequencies / factor); ``llama3`` (piecewise
    wavelength-dependent rescale with smooth interpolation band); ``yarn``
    (NTK-by-parts ramp between interpolated and extrapolated frequencies,
    mscale attention factor — DeepSeek/Qwen long-context); ``longrope``
    (per-dimension short/long factor tables — Phi-3 128k; ``seq_len``, a
    STATIC python int, selects the table like HF does from the runtime
    length). Others (``dynamic`` NTK) raise rather than silently
    mis-rotate.

    longrope deployment contract (static shapes, unlike HF's per-forward
    dynamic switch): plain forwards select by the input length; EVERY
    cached-decode call — prefill included, generation.py always primes the
    cache with ``decode=True`` — selects by the cache capacity
    (``max_position_embeddings``), so one session never mixes rotary
    tables. Deploying a 128k longrope checkpoint for short sessions?
    Set ``max_position_embeddings`` to the session bound (e.g. 4096) and
    the short table applies, matching HF for sub-original lengths — this
    is also the knob Phi-3's own model card prescribes."""
    import math

    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if not scaling:
        return freqs, 1.0
    rope_type = scaling.get("rope_type", scaling.get("type", "default"))
    if rope_type == "default":
        return freqs, 1.0
    if rope_type == "linear":
        return freqs / float(scaling["factor"]), 1.0
    if rope_type == "llama3":
        factor = float(scaling["factor"])
        low_freq_factor = float(scaling.get("low_freq_factor", 1.0))
        high_freq_factor = float(scaling.get("high_freq_factor", 4.0))
        orig = float(scaling.get("original_max_position_embeddings", 8192))
        low_freq_wavelen = orig / low_freq_factor
        high_freq_wavelen = orig / high_freq_factor
        wavelen = 2.0 * jnp.pi / freqs
        # long wavelengths fully scaled, short ones untouched, the band
        # between interpolated (HF _compute_llama3_parameters)
        smooth = (orig / wavelen - low_freq_factor) / (high_freq_factor - low_freq_factor)
        smoothed = (1.0 - smooth) * freqs / factor + smooth * freqs
        scaled = jnp.where(wavelen > low_freq_wavelen, freqs / factor, smoothed)
        return jnp.where(wavelen < high_freq_wavelen, freqs, scaled), 1.0
    if rope_type == "yarn":
        factor = float(scaling["factor"])
        orig = float(scaling.get("original_max_position_embeddings") or orig_max or max_pos or 0)
        if not orig:
            raise ValueError("yarn rope_scaling needs original_max_position_embeddings or max_pos")
        attention_factor = scaling.get("attention_factor")
        mscale, mscale_all_dim = scaling.get("mscale"), scaling.get("mscale_all_dim")

        def get_mscale(scale, m=1.0):
            return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

        if attention_factor is None:
            if mscale and mscale_all_dim:
                attention_factor = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
            else:
                attention_factor = get_mscale(factor)
        beta_fast = scaling.get("beta_fast") or 32
        beta_slow = scaling.get("beta_slow") or 1

        def correction_dim(num_rotations):
            return d * math.log(orig / (num_rotations * 2 * math.pi)) / (2 * math.log(theta))

        low, high = correction_dim(beta_fast), correction_dim(beta_slow)
        if scaling.get("truncate", True):
            low, high = math.floor(low), math.ceil(high)
        low, high = max(low, 0), min(high, d - 1)
        if low == high:
            high += 0.001  # HF's singularity guard
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
        extrapolation_factor = 1.0 - ramp
        inv = freqs / factor * (1 - extrapolation_factor) + freqs * extrapolation_factor
        return inv, float(attention_factor)
    if rope_type == "dynamic":
        # dynamic NTK: the base grows with the deployed length so the
        # longest wavelength always spans it (HF _compute_dynamic_ntk_
        # parameters with seq_len pinned to the static deployment length —
        # HF recomputes per forward, we specialize per compiled shape)
        factor = float(scaling["factor"])
        # NO max_pos fallback here: orig == deployed bound makes the formula
        # cancel to base == theta — the scaling silently disabled exactly
        # when the user relied on the guess (unlike yarn, where a wrong
        # orig at least changes the numbers)
        orig = float(scaling.get("original_max_position_embeddings") or orig_max or 0)
        if not orig:
            raise ValueError(
                "dynamic rope_scaling needs the ORIGINAL context length — put "
                "original_max_position_embeddings in the rope_scaling dict or set "
                "LlamaConfig.original_max_position_embeddings (HF stores it as the "
                "checkpoint's top-level max_position_embeddings)"
            )
        length = float(max(seq_len or 0, orig))
        base = theta * ((factor * length / orig) - (factor - 1)) ** (d / (d - 2))
        return 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)), 1.0
    if rope_type == "longrope":
        # HF's config.json stores original_max_position_embeddings at the
        # TOP level for Phi-3; accept it inside the dict or via orig_max,
        # and refuse to guess — a silent max_pos fallback would pin the
        # short table forever with attention factor 1.0
        orig = int(scaling.get("original_max_position_embeddings") or orig_max or 0)
        if not orig:
            raise ValueError(
                "longrope rope_scaling needs original_max_position_embeddings — put it in "
                "the rope_scaling dict or set LlamaConfig.original_max_position_embeddings "
                "(HF config.json keeps it at the top level)"
            )
        factor = scaling.get("factor")
        if max_pos:
            factor = max_pos / orig
        attention_factor = scaling.get("attention_factor")
        if attention_factor is None:
            attention_factor = (
                1.0 if not factor or factor <= 1.0 else math.sqrt(1 + math.log(factor) / math.log(orig))
            )
        use_long = seq_len is not None and seq_len > orig
        ext = jnp.asarray(scaling["long_factor" if use_long else "short_factor"], jnp.float32)
        return freqs / ext, float(attention_factor)
    raise NotImplementedError(
        f"rope_scaling type {rope_type!r} is not supported "
        "(default/linear/llama3/yarn/longrope/dynamic are); "
        "a silent fallback would mis-rotate every position"
    )


def rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    scaling: Optional[dict] = None,
    *,
    max_pos: Optional[int] = None,
    seq_len: Optional[int] = None,
    orig_max: Optional[int] = None,
) -> jax.Array:
    """Rotary embedding over the last dim of [B, S, H, D]."""
    d = x.shape[-1]
    freqs, attn_factor = rope_frequencies(
        d, theta, scaling, max_pos=max_pos, seq_len=seq_len, orig_max=orig_max
    )
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,D/2]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    if attn_factor != 1.0:
        cos, sin = cos * attn_factor, sin * attn_factor
    x1, x2 = x[..., ::2], x[..., 1::2]
    rotated = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.reshape(x.shape).astype(x.dtype)


def _dispatch_attention(
    q, k, v, impl: str, sliding_window: Optional[int] = None, scale=None, logit_softcap=None,
    forward_only: bool = False,
):
    """Pick the attention path: context-parallel (ring / all-to-all) when
    the active mesh has a non-trivial ``seq`` axis, else dense/flash. This
    is where long-context becomes a *layout* decision rather than a model
    rewrite (SURVEY §5). ``sliding_window`` adds a Mistral-style band on
    EVERY path: the XLA mask at short lengths, the banded flash kernel
    (O(S*W)) at flash lengths on TPU, and absolute-position masking
    inside the ring / all-to-all schedules on seq-sharded meshes.
    ``forward_only``: the caller never differentiates this call (a
    prefill that starts a cache), so the dense path chooses its kernel
    by the forward pass alone (``ops.attention.prefers_flash``)."""
    if impl not in ("auto", "ring", "all_to_all", "dense"):
        raise ValueError(f"attention_impl must be auto|ring|all_to_all|dense, got {impl!r}")
    mesh = None
    if impl != "dense":
        from ..ops.attention import active_mesh

        mesh = active_mesh()
    seq_ok = mesh is not None and "seq" in mesh.shape and mesh.shape["seq"] > 1
    if impl in ("ring", "all_to_all") and not seq_ok:
        # an explicit request must not silently fall back to the O(S^2) path
        raise ValueError(
            f"attention_impl={impl!r} requires an active mesh with a seq axis > 1 "
            f"(got {dict(mesh.shape) if mesh is not None else None}); use 'auto' for adaptive dispatch"
        )
    if seq_ok:
        from ..parallel.context import context_parallel_attention

        if logit_softcap is not None:
            raise NotImplementedError(
                "attention logit softcapping (Gemma2) is not supported inside the "
                "ring/all-to-all context-parallel schedules; use a mesh without a seq axis"
            )
        method = "all_to_all" if impl == "all_to_all" else "ring"
        return context_parallel_attention(
            q, k, v, mesh=mesh, causal=True, method=method, window=sliding_window, scale=scale
        )
    from ..ops.attention import dot_product_attention

    # the op folds the band (if any) into the XLA mask at short lengths
    # and runs the banded flash kernel (O(S*W)) at flash lengths on TPU
    # (the op's auto-dispatch avoids the flash kernel when a softcap is
    # set — the kernel has no tanh-cap branch)
    return dot_product_attention(
        q, k, v, causal=True, mesh=mesh, window=sliding_window, scale=scale,
        logit_softcap=logit_softcap, forward_only=forward_only,
    )


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden, positions, decode: bool = False, new_span=None):
        cfg = self.config
        head_dim = cfg.head_dim or cfg.hidden_size // cfg.num_attention_heads
        q = _dense(cfg, cfg.num_attention_heads * head_dim, "q_proj", hidden.dtype, cfg.qkv_bias)(hidden)
        k = _dense(cfg, cfg.num_key_value_heads * head_dim, "k_proj", hidden.dtype, cfg.qkv_bias)(hidden)
        v = _dense(cfg, cfg.num_key_value_heads * head_dim, "v_proj", hidden.dtype, cfg.qkv_bias)(hidden)
        if cfg.qk_norm_flat:
            # OLMo2: RMSNorm over the FLAT projection (all heads jointly)
            # before the head split — a different statistic than per-head
            q = RMSNorm(cfg.rms_norm_eps, cfg.norm_plus_one, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, cfg.norm_plus_one, name="k_norm")(k)
        q = q.reshape(*q.shape[:-1], cfg.num_attention_heads, head_dim)
        k = k.reshape(*k.shape[:-1], cfg.num_key_value_heads, head_dim)
        v = v.reshape(*v.shape[:-1], cfg.num_key_value_heads, head_dim)
        if cfg.qk_norm:
            # per-head RMSNorm over head_dim (Qwen3): the mean-of-squares is
            # permutation-invariant, so the interleaved rope layout only
            # requires the imported scale vector to be re-paired (hub.py)
            q = RMSNorm(cfg.rms_norm_eps, cfg.norm_plus_one, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, cfg.norm_plus_one, name="k_norm")(k)
        # longrope's short/long table selection needs a STATIC length hint:
        # prefill uses the (static) input length like HF's runtime switch;
        # decode sees S=1, so the cache capacity stands in for it
        rope_len = cfg.max_position_embeddings if decode else hidden.shape[1]
        if cfg.rope_theta is not None:
            turn = functools.partial(
                rope, positions=positions, theta=cfg.rope_theta, scaling=cfg.rope_scaling,
                max_pos=cfg.max_position_embeddings, seq_len=rope_len, orig_max=cfg.original_max_position_embeddings)
            turned = int(head_dim * cfg.partial_rotary_factor)
            if turned == head_dim:
                q, k = turn(q), turn(k)
            else:  # rotary turns the first ``partial_rotary_factor`` of a head's values, under frequencies of that width
                q = jnp.concatenate([turn(q[..., :turned]), q[..., turned:]], axis=-1)
                k = jnp.concatenate([turn(k[..., :turned]), k[..., turned:]], axis=-1)
        scale = None  # attention default: head_dim**-0.5
        if cfg.query_pre_attn_scalar is not None:
            scale = float(cfg.query_pre_attn_scalar) ** -0.5  # Gemma2
        if cfg.attention_multiplier is not None:
            scale = float(cfg.attention_multiplier)  # Granite: the scale itself, not a power of the head size
        if cfg.attention_class is not None:
            out = self._eva_attention(q, k, v, scale, decode)
        elif decode:
            # a device operation's name carries the kind of layer it belongs to
            with jax.named_scope("attn.full" if cfg.sliding_window is None else "attn.window"):
                out = self._cold_or_cached_attention(q, k, v, scale, new_span)
        else:
            out = _dispatch_attention(
                q, k, v, cfg.attention_impl, cfg.sliding_window,
                scale=scale, logit_softcap=cfg.attn_logit_softcap,
            )
        if cfg.attn_gate:
            with jax.named_scope("attn.gate"):
                gate = _dense(cfg, cfg.num_attention_heads, "g_proj", hidden.dtype)(hidden)  # [.., H]: a scalar a head
                out = out * jax.nn.softplus(gate.astype(jnp.float32)).astype(out.dtype)[..., None]
        out = out.reshape(*out.shape[:-2], cfg.num_attention_heads * head_dim)
        return _dense(cfg, cfg.hidden_size, "o_proj", hidden.dtype)(out)

    def _cold_or_cached_attention(self, q, k, v, scale, new_span):
        """``decode=True``: the call that starts a dense cache stores its rows and attends over them alone,
        through :func:`_dispatch_attention` as a call nobody differentiates (``cached_attention`` would score
        them against all ``max_len`` rows of a cache that was empty a moment ago: ``[heads, S, max_len]``
        float32); every later call (a decode step, a warm chunk window, the paged layout) is
        :meth:`_cached_attention`'s. ``"dense"``: a cache's rows are not sharded over ``seq``, so no call with
        a cache takes the ring / all-to-all schedules, whatever ``attention_impl`` says of a training step."""
        from ..ops import kv_cache, paged_kv

        cfg = self.config
        if paged_kv.active_paged_config() is not None or self.has_variable("cache", "key"):
            return self._cached_attention(q, k, v, scale, new_span)
        kv_cache.start_cache(self, k, v, cfg.max_position_embeddings)
        return _dispatch_attention(
            q, k, v, "dense", cfg.sliding_window, scale=scale, logit_softcap=cfg.attn_logit_softcap, forward_only=True)

    def _cached_attention(self, q, k, v, scale=None, new_span=None):
        """KV-cache incremental attention (generation path; shared cache
        machinery in :mod:`accelerate_tpu.ops.kv_cache`)."""
        from ..ops.kv_cache import cached_attention

        return cached_attention(
            self, q, k, v, self.config.max_position_embeddings,
            scale=scale,
            sliding_window=self.config.sliding_window,
            logit_softcap=self.config.attn_logit_softcap,
            keep_rows_before=None if new_span is None else new_span[0],
        )


    def _eva_attention(self, q, k, v, scale, decode: bool):
        """EVA (:mod:`accelerate_tpu.ops.eva_attention`): the window's exact rows and the pooled chunks of the
        windows before it, under one softmax; with a cache when ``decode``."""
        from ..ops.eva_attention import eva_cached_attention, eva_prefill_attention

        cfg = self.config
        if cfg.attention_class != "eva":
            raise ValueError(f"attention_class must be None or 'eva', got {cfg.attention_class!r}")
        if cfg.num_key_value_heads != cfg.num_attention_heads or cfg.sliding_window is not None:
            raise NotImplementedError(
                "EVA attention pools a chunk a head under vectors of its own: as many key/value heads as query "
                "heads, and no sliding band beside the aligned window")
        heads = nn.initializers.normal(0.02)
        mu = self.param("adaptive_mu_k", heads, k.shape[-2:])
        phi = self.param("adaptive_phi", heads, k.shape[-2:])
        sizes = {"window": cfg.eva_window_size, "chunk": cfg.eva_chunk_size}
        if decode:
            return eva_cached_attention(self, q, k, v, mu, phi, cfg.max_position_embeddings, scale=scale, **sizes)
        scale = k.shape[-1] ** -0.5 if scale is None else scale
        return eva_prefill_attention(q, k, v, mu, phi, scale=scale, **sizes)[0]


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        gate = _dense(cfg, cfg.intermediate_size, "gate_proj", hidden.dtype)(hidden)
        up = _dense(cfg, cfg.intermediate_size, "up_proj", hidden.dtype)(hidden)
        if cfg.mlp_activation == "silu":
            act = nn.silu(gate)
        elif cfg.mlp_activation == "gelu_tanh":
            act = nn.gelu(gate, approximate=True)
        else:
            raise ValueError(f"mlp_activation must be silu|gelu_tanh, got {cfg.mlp_activation!r}")
        return _dense(cfg, cfg.hidden_size, "down_proj", hidden.dtype)(act * up)


class LatentAttention(nn.Module):
    """Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434, as the
    V3-style checkpoints publish it). Queries through a rank-``q_lora_rank``
    bottleneck with an RMSNorm; keys and values from ONE compressed row a
    token, ``[c_kv ; k_rope]`` (``kv_lora_rank`` normed values and a rotary
    key of ``qk_rope_head_dim`` shared by all heads), which is all the cache
    holds. Rotary pairs are ``(2i, 2i+1)`` (``rope_interleave``).

    Two paths, the same mathematics. Without a cache, and for a cold prefill
    (``decode=True`` with no cache yet), K and V are decompressed per head
    (``kv_b_proj``: ``qk_nope_head_dim + v_head_dim`` a head) and ordinary
    causal attention runs over the new tokens; a cold prefill also stores
    the rows. Against a cache (decode steps, warm chunk windows) ``W_UK`` is
    absorbed into the query and ``W_UV`` into the output, so the cache is
    never decompressed: multi-query attention at head size ``kv_lora_rank +
    qk_rope_head_dim`` over the shared rows."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden, positions, decode: bool = False):
        cfg = self.config
        heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rot, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        dt = hidden.dtype
        if cfg.q_lora_rank is None:
            raise NotImplementedError("latent attention with a full-rank query projection (q_lora_rank None)")
        c_q = RMSNorm(cfg.rms_norm_eps, name="q_a_norm")(_dense(cfg, cfg.q_lora_rank, "q_a_proj", dt)(hidden))
        q = _dense(cfg, heads * (nope + rot), "q_b_proj", dt)(c_q)
        q = q.reshape(*q.shape[:-1], heads, nope + rot)
        rope_kw = dict(max_pos=cfg.max_position_embeddings, orig_max=cfg.original_max_position_embeddings,
                       seq_len=cfg.max_position_embeddings if decode else hidden.shape[1])
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:], positions, cfg.rope_theta, cfg.rope_scaling, **rope_kw)
        kv_a = _dense(cfg, rank + rot, "kv_a_proj", dt)(hidden)
        c_kv = RMSNorm(cfg.rms_norm_eps, name="kv_a_norm")(kv_a[..., :rank])
        k_rope = rope(kv_a[..., None, rank:], positions, cfg.rope_theta, cfg.rope_scaling, **rope_kw)  # [B, S, 1, rot]
        # W_kvb, a head at a time: [rank, H, nope + v]; W_UK its first nope columns, W_UV the rest
        w_kvb = self.param("kv_b_proj", nn.initializers.lecun_normal(), (rank, heads, nope + vd)).astype(dt)
        scale = float(nope + rot) ** -0.5
        rows = jnp.concatenate([c_kv, k_rope[..., 0, :]], axis=-1)  # [B, S, rank + rot]: what the cache holds
        from ..ops import kv_cache, paged_kv

        cold = decode and paged_kv.active_paged_config() is None and not self.has_variable("cache", "latent")
        if decode and not cold:
            with jax.named_scope("mla.absorb"):
                q_lat = jnp.concatenate([jnp.einsum("bshn,chn->bshc", q_nope, w_kvb[..., :nope]), q_rope], axis=-1)
            o_lat = kv_cache.cached_latent_attention(
                self, q_lat, rows, cfg.max_position_embeddings, value_width=rank, scale=scale
            )
            with jax.named_scope("mla.absorb"):
                out = jnp.einsum("bshc,chv->bshv", o_lat, w_kvb[..., nope:])
        else:
            if cold:  # store the rows; attention itself needs only the new tokens
                cache, idx = kv_cache.latent_cache_variables(
                    self, rows.shape[0], cfg.max_position_embeddings, rank + rot, dt
                )
                cache.value = jax.lax.dynamic_update_slice(cache.value, rows, (0, 0, 0))
                idx.value = jnp.asarray(rows.shape[1], jnp.int32)
            kv = jnp.einsum("bsc,chd->bshd", c_kv, w_kvb)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (*kv.shape[:-1], rot))], axis=-1)
            q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
            # one head size for the dispatched kernels: v padded with zeros to the keys' width, cut after
            v = jnp.pad(kv[..., nope:], ((0, 0),) * 3 + ((0, nope + rot - vd),))
            out = _dispatch_attention(q_full, k, v, cfg.attention_impl, scale=scale, forward_only=cold)[..., :vd]
        out = out.reshape(*out.shape[:-2], heads * vd)
        return _dense(cfg, cfg.hidden_size, "o_proj", dt)(out)


class RoutedFFN(nn.Module):
    """Routed SwiGLU experts with shared experts, no capacity
    (:mod:`accelerate_tpu.ops.moe` ``dropless_moe_ffn``). The scores follow
    ``scoring_func``: ``"sigmoid"`` is DeepSeek-V3-style ``noaux_tc``
    routing with one group (scores in float32, a selection bias that
    chooses and does not weigh, normalised top-k weights times
    ``routed_scaling_factor``); ``"softmax_topk"`` is Granite's (the
    largest raw logits, a softmax over those alone, no bias and no
    scaling). It sows the experts' load of each call into the
    ``expert_load`` collection, for whoever makes that mutable.
    ``row_valid`` (bool, ``hidden``'s leading axes) names the tokens that
    count: the others reach no routed expert and get the shared expert
    alone; None means every token.

    Under ``expert_shares`` > 1 the layer holds its share of the experts
    (``LlamaConfig.held_experts``; the stacked matrices are ``[held, ..]``)
    and routes over all of them: it returns the held experts' part of the
    routed sum plus the shared expert, and the four ``expert_load`` counts
    are of held experts alone."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden, row_valid=None):
        from ..ops.moe import EXPERT_LOAD, dropless_moe_ffn, expert_load, sigmoid_topk_routing, softmax_topk_routing

        cfg = self.config
        if cfg.scoring_func not in ("sigmoid", "softmax_topk"):
            raise NotImplementedError(
                f"routed experts score by sigmoid or by a softmax over the top k, got scoring_func={cfg.scoring_func!r}")
        d, e, ff = cfg.hidden_size, cfg.n_routed_experts, cfg.moe_intermediate_size
        first, held = cfg.held_experts
        init = nn.initializers.lecun_normal()
        router = self.param("router/kernel", init, (d, e))
        if cfg.scoring_func == "sigmoid":
            bias = self.param("router/e_score_correction_bias", nn.initializers.zeros, (e,))
        gate = self.param("experts/gate_proj", init, (held, d, ff))
        up = self.param("experts/up_proj", init, (held, d, ff))
        down = self.param("experts/down_proj", init, (held, ff, d))
        flat = hidden.reshape(-1, d)
        with jax.named_scope("moe.route"):
            # float32 as published; ``highest`` because a TPU's default float32 product is one bfloat16 pass
            logits = jnp.matmul(flat.astype(jnp.float32), router.astype(jnp.float32), precision="highest")
            if cfg.scoring_func == "sigmoid":
                experts, weights = sigmoid_topk_routing(
                    logits, bias, cfg.num_experts_per_tok, cfg.norm_topk_prob, cfg.routed_scaling_factor
                )
            else:
                experts, weights = softmax_topk_routing(logits, cfg.num_experts_per_tok)
        out, group_sizes = dropless_moe_ffn(
            flat, experts, weights, gate, up, down, None if row_valid is None else row_valid.reshape(-1),
            first_expert=None if cfg.expert_shares == 1 else first,
        )
        if self.is_mutable_collection(EXPERT_LOAD):
            self.sow(EXPERT_LOAD, "counts", expert_load(group_sizes, experts.size))
        out = out.reshape(hidden.shape)
        shared_width = cfg.shared_intermediate_size or ff * cfg.n_shared_experts
        if shared_width:
            with jax.named_scope("moe.shared"):
                shared = dataclasses.replace(cfg, intermediate_size=shared_width)
                out = out + LlamaMLP(shared, name="shared_experts")(hidden)
        return out


class MambaMixer(nn.Module):
    """Selective state-space mixer (Mamba-1, arXiv:2312.00752) as the Jamba
    checkpoints run it: ``[u, z] = in_proj(x)``; ``u = silu(conv1d(u))``
    (depthwise, causal, ``mamba_d_conv`` taps); ``[dt, B, C] = x_proj(u)``,
    each through an RMSNorm of its own (Jamba's ``dt_layernorm``,
    ``b_layernorm``, ``c_layernorm``); ``delta = softplus(dt_proj(dt))``;
    ``A = -exp(A_log)``; the recurrence of :mod:`accelerate_tpu.ops.selective_scan`;
    ``out_proj(y * silu(z))``. ``exp``, ``softplus`` and the state are
    float32; weights and activations keep the stream's type.

    With ``decode=True`` the layer keeps, in the ``cache`` collection and in
    the dense and the paged serving layout alike, ``ssm_state`` ``[B,
    d_state, d_inner]`` float32 (``d_inner`` along the lanes) and
    ``conv_state`` ``[B, (d_conv - 1) * d_inner]`` (the last inputs of the
    convolution, oldest first, one row a sequence so that no minor axis of 3
    is padded to a tile): one row a sequence whatever its length, no pages.
    ``new_span`` ``(lo, hi)`` names the window's new tokens; the others (a
    bucket's right pad, a chunk window's overlapped head) leave the state
    as it was. Without a cache the same scan runs from a zero state.
    ``row_valid`` (bool ``[B, 1]``, a paged decode tick's slots in which a
    request decodes) reaches the step kernel alone: the other slots'
    ``ssm_state`` is neither read nor written and their ``y`` is zeros. The
    projections and the convolution run on every row (their bytes are
    weights), and the plain scan takes no mask."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden, decode: bool = False, new_span=None, row_valid=None):
        from ..ops import paged_kv
        from ..ops.selective_scan import causal_conv1d, selective_scan

        cfg = self.config
        dt, f32 = hidden.dtype, jnp.float32
        bsz, t, _ = hidden.shape
        d_in, n, k = cfg.mamba_expand * cfg.hidden_size, cfg.mamba_d_state, cfg.mamba_d_conv
        rank = cfg.mamba_dt_rank or -(-cfg.hidden_size // 16)
        lecun = nn.initializers.lecun_normal()
        conv_w = self.param("conv_kernel", lecun, (k, d_in))
        conv_b = self.param("conv_bias", nn.initializers.zeros, (d_in,)) if cfg.mamba_conv_bias else None
        w_dt = self.param("dt_proj", lecun, (rank, d_in))
        # Mamba's own initialiser: softplus(dt_bias) log-uniform in [0.001, 0.1], A = -(1 .. d_state), D = 1
        dt_bias = self.param("dt_bias", _mamba_dt_bias_init, (d_in,))
        a_log = self.param("A_log", lambda _k, shape: jnp.broadcast_to(jnp.log(jnp.arange(1.0, n + 1))[:, None], shape), (n, d_in))
        d_skip = self.param("D", nn.initializers.ones, (d_in,))

        if decode:
            ssm = self.variable("cache", "ssm_state", jnp.zeros, (bsz, n, d_in), f32)
            conv = self.variable("cache", "conv_state", jnp.zeros, (bsz, (k - 1) * d_in), dt)
            h0, carried = ssm.value, conv.value
        else:
            h0, carried = jnp.zeros((bsz, n, d_in), f32), jnp.zeros((bsz, (k - 1) * d_in), dt)
        lo, hi = (0, t) if new_span is None else new_span

        with jax.named_scope("ssm.proj"):
            xz = _dense(cfg, 2 * d_in, "in_proj", dt, cfg.mamba_proj_bias)(hidden)
            u, z = xz[..., :d_in], xz[..., d_in:]
        with jax.named_scope("ssm.conv"):
            u, carried = causal_conv1d(u, conv_w, conv_b, carried, lo, hi)
            u = nn.silu(u)
        with jax.named_scope("ssm.proj"):
            dbc = _dense(cfg, rank + 2 * n, "x_proj", dt)(u)
            step = RMSNorm(cfg.rms_norm_eps, name="dt_norm")(dbc[..., :rank])
            b_t = RMSNorm(cfg.rms_norm_eps, name="b_norm")(dbc[..., rank : rank + n])
            c_t = RMSNorm(cfg.rms_norm_eps, name="c_norm")(dbc[..., rank + n :])
            delta = jax.nn.softplus(
                jnp.matmul(step, w_dt.astype(dt), preferred_element_type=f32) + dt_bias.astype(f32)
            )
            a = -jnp.exp(a_log.astype(f32))
        if decode and t == 1 and paged_kv.active_paged_config() is not None and state_step_kernel():
            # the serving tick's step: one pass over the state of the slots that decode (``row_valid``; every
            # slot without it), in place (a vmapped dense tick and generate() take the plain step below: no
            # kernel under vmap, and no mask either)
            from ..ops.pallas_selective_scan import ssm_state_step

            with jax.named_scope("ssm.step"):
                y, h = ssm_state_step(
                    h0, u[:, 0], delta[:, 0], b_t[:, 0], c_t[:, 0], a, d_skip,
                    None if row_valid is None else row_valid.reshape(bsz),
                    interpret=jax.default_backend() != "tpu",
                )
                y = y[:, None]
        else:
            with jax.named_scope("ssm.step" if t == 1 else "ssm.scan"):
                y, h = selective_scan(u, delta, a, b_t, c_t, d_skip, h0, lo, hi)
        if decode:
            ssm.value, conv.value = h, carried
        with jax.named_scope("ssm.proj"):
            return _dense(cfg, cfg.hidden_size, "out_proj", dt, cfg.mamba_proj_bias)(y.astype(dt) * nn.silu(z))


class Mamba2Mixer(nn.Module):
    """State-space duality mixer (Mamba-2, arXiv:2405.21060) as the
    ``granitemoehybrid`` checkpoints run it, one group: ``[z | xBC | dt] =
    in_proj(x)`` (``d_inner``, ``d_inner + 2 N`` and ``heads`` wide, no
    bias); ``xBC = silu(conv1d(xBC))`` (depthwise, causal, ``mamba_d_conv``
    taps, with a bias); ``[x' | B | C] = xBC``, ``x'`` as ``[heads, d_head]``;
    ``delta = softplus(dt + dt_bias)`` a head (no clamp); ``A = -exp(A_log)``
    a head; the recurrence of :mod:`accelerate_tpu.ops.ssd_scan`;
    ``RMSNorm_w(y * silu(z))`` over all of ``d_inner`` (the gate before the
    norm, one group); ``out_proj``. ``exp``, ``softplus``, the recurrence
    and the state are float32; weights and activations keep the stream's type.

    With ``decode=True`` the layer keeps, in the ``cache`` collection and in
    the dense and the paged serving layout alike, ``ssm_state`` ``[B,
    d_state, d_inner]`` float32 (the heads' channels side by side along
    the lanes: 4 MB a sequence at 128 x 8192) and ``conv_state`` ``[B,
    (d_conv - 1) * (d_inner + 2 N)]``: the leaves :class:`MambaMixer` keeps,
    under the same names (:data:`ops.paged_kv.STATE_LEAVES`), so paste,
    clearing, the hand-off refusals and the row mask need no second list.
    ``new_span`` and ``row_valid`` as :class:`MambaMixer` takes them: a
    prefill window runs the chunked scan
    (:func:`~accelerate_tpu.ops.ssd_scan.ssd_scan`), the paged tick's step
    the kernel :func:`~accelerate_tpu.ops.pallas_ssd_step.ssd_state_step`
    over the slots that decode, any other step the plain one."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden, decode: bool = False, new_span=None, row_valid=None):
        from ..ops import paged_kv
        from ..ops.selective_scan import causal_conv1d
        from ..ops.ssd_scan import ssd_scan, ssd_state_step_plain

        cfg = self.config
        dt, f32 = hidden.dtype, jnp.float32
        bsz, t, _ = hidden.shape
        heads, p, n, k = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_d_conv
        d_in = heads * p
        if cfg.mamba_n_groups != 1 or d_in != cfg.mamba_expand * cfg.hidden_size:
            raise NotImplementedError(
                f"Mamba-2 layers are built with one group of B / C and heads x d_head = expand x hidden, got "
                f"mamba_n_groups={cfg.mamba_n_groups}, {heads} x {p} against {cfg.mamba_expand} x {cfg.hidden_size}")
        conv_dim = d_in + 2 * n
        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(), (k, conv_dim))
        conv_b = self.param("conv_bias", nn.initializers.zeros, (conv_dim,)) if cfg.mamba_conv_bias else None
        # Mamba-2's own initialiser: softplus(dt_bias) log-uniform in [0.001, 0.1], A uniform in 1 .. 16, D = 1
        dt_bias = self.param("dt_bias", _mamba_dt_bias_init, (heads,))
        a_log = self.param("A_log", lambda key, shape: jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0)), (heads,))
        d_skip = self.param("D", nn.initializers.ones, (heads,))

        if decode:
            ssm = self.variable("cache", "ssm_state", jnp.zeros, (bsz, n, d_in), f32)
            conv = self.variable("cache", "conv_state", jnp.zeros, (bsz, (k - 1) * conv_dim), dt)
            h0, carried = ssm.value, conv.value
        else:
            h0, carried = jnp.zeros((bsz, n, d_in), f32), jnp.zeros((bsz, (k - 1) * conv_dim), dt)
        lo, hi = (0, t) if new_span is None else new_span

        with jax.named_scope("ssd.proj"):
            zxd = _dense(cfg, 2 * d_in + 2 * n + heads, "in_proj", dt, cfg.mamba_proj_bias)(hidden)
            z, xbc, step = zxd[..., :d_in], zxd[..., d_in : d_in + conv_dim], zxd[..., d_in + conv_dim :]
            delta = jax.nn.softplus(step.astype(f32) + dt_bias.astype(f32))  # [B, T, heads]
            a = -jnp.exp(a_log.astype(f32))
        with jax.named_scope("ssd.conv"):
            xbc, carried = causal_conv1d(xbc, conv_w, conv_b, carried, lo, hi)
            xbc = nn.silu(xbc)
            x = xbc[..., :d_in].reshape(bsz, t, heads, p)
            b_t, c_t = xbc[..., d_in : d_in + n], xbc[..., d_in + n :]
        if decode and t == 1 and paged_kv.active_paged_config() is not None and state_step_kernel():
            # the serving tick's step: one pass over the state of the slots that decode (``row_valid``; every
            # slot without it), in place (a vmapped dense tick and generate() take the plain step below)
            from ..ops.pallas_ssd_step import ssd_state_step

            with jax.named_scope("ssd.step"):
                y, h = ssd_state_step(
                    h0, x[:, 0], delta[:, 0], a, b_t[:, 0], c_t[:, 0], d_skip,
                    None if row_valid is None else row_valid.reshape(bsz),
                    interpret=jax.default_backend() != "tpu",
                )
                y = y[:, None]
        elif t == 1 and new_span is None:
            with jax.named_scope("ssd.step"):
                y, h = ssd_state_step_plain(h0, x[:, 0], delta[:, 0], a, b_t[:, 0], c_t[:, 0], d_skip)
                y = y[:, None]
        else:
            with jax.named_scope("ssd.scan"):
                y, h = ssd_scan(x, delta, a, b_t, c_t, d_skip, h0, lo, hi, chunk=cfg.mamba_chunk_size)
        if decode:
            ssm.value, conv.value = h, carried
        with jax.named_scope("ssd.proj"):
            gated = y.reshape(bsz, t, d_in) * nn.silu(z.astype(f32))  # float32: the gate before the norm
            normed = RMSNorm(cfg.rms_norm_eps, name="norm")(gated).astype(dt)
            return _dense(cfg, cfg.hidden_size, "out_proj", dt, cfg.mamba_proj_bias)(normed)


class ShortConvMixer(nn.Module):
    """Gated short convolution (LFM2's ``conv`` operator): ``[B, C, x] =
    split3(in_proj(u))``, in that order; ``y = C * conv1d(B * x)``, a causal
    depthwise convolution of ``conv_L_cache`` taps; ``out_proj(y)``. No
    activation and no gate besides ``B`` and ``C``; ``conv_bias`` puts a
    bias on the two projections and the convolution alike.

    With ``decode=True`` the layer keeps ``conv_state`` ``[B, (conv_L_cache -
    1) * hidden]`` in the ``cache`` collection, in the dense and the paged
    serving layout alike: the last ``B * x`` rows before the next token,
    oldest first, one lane-dense row a sequence whatever its length
    (:func:`~accelerate_tpu.ops.selective_scan.causal_conv1d`). The published
    code caches ``conv_L_cache`` columns; the convolution reads ``conv_L_cache
    - 1`` of them beside the token itself, and that many are carried.
    ``new_span`` as :class:`MambaMixer` takes it; without a cache the
    convolution runs from zeros."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden, decode: bool = False, new_span=None):
        from ..ops.selective_scan import causal_conv1d

        cfg = self.config
        dt = hidden.dtype
        bsz, t, d = hidden.shape
        k = cfg.conv_L_cache
        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(), (k, d))
        conv_b = self.param("conv_bias", nn.initializers.zeros, (d,)) if cfg.conv_bias else None
        if decode:
            conv = self.variable("cache", "conv_state", jnp.zeros, (bsz, (k - 1) * d), dt)
            carried = conv.value
        else:
            carried = jnp.zeros((bsz, (k - 1) * d), dt)
        lo, hi = (0, t) if new_span is None else new_span
        with jax.named_scope("conv.proj"):
            bcx = _dense(cfg, 3 * d, "in_proj", dt, cfg.conv_bias)(hidden)
            b_gate, c_gate, x = bcx[..., :d], bcx[..., d : 2 * d], bcx[..., 2 * d :]
        with jax.named_scope("conv.mix"):
            y, carried = causal_conv1d(b_gate * x, conv_w, conv_b, carried, lo, hi)
            y = c_gate * y
        if decode:
            conv.value = carried
        with jax.named_scope("conv.out"):
            return _dense(cfg, d, "out_proj", dt, cfg.conv_bias)(y)


def _mamba_dt_bias_init(key, shape, dtype=jnp.float32):
    step = jnp.exp(jax.random.uniform(key, shape) * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)  # softplus^-1


def _one_device() -> bool:
    """XLA's partitioner cannot split a ``pallas_call``: a kernel with no ``shard_map`` of its own is for one device."""
    from ..ops.attention import active_mesh

    mesh = active_mesh()
    return mesh is None or mesh.size == 1


def state_step_kernel() -> bool:
    """Whether a paged decode step traced here steps a state-space layer's state through its kernel
    (:func:`~accelerate_tpu.ops.pallas_selective_scan.ssm_state_step`, or a Mamba-2 layer's
    :func:`~accelerate_tpu.ops.pallas_ssd_step.ssd_state_step`), which visits the slots ``row_valid``
    names alone; the plain step (off the chip, across a mesh) steps every slot."""
    from ..ops import paged_kv

    return (jax.default_backend() == "tpu" or paged_kv.FORCE_KERNEL_INTERPRET) and _one_device()


class LlamaLayer(nn.Module):
    config: LlamaConfig
    routed: bool = False  # the FFN is RoutedFFN (a layer past ``first_k_dense_replace`` of a config with experts)
    mixer: str = "attention"  # ``LlamaConfig.mixer_kind`` of this layer: "attention" | "mamba" | "mamba2" | "conv"

    @nn.compact
    def __call__(self, hidden, positions, decode: bool = False, new_span=None, row_valid=None):
        cfg = self.config
        attn_cls = LlamaAttention if cfg.kv_lora_rank is None else LatentAttention

        def attn(x):
            if self.mixer == "mamba":
                return MambaMixer(cfg, name="mamba")(x, decode, new_span, row_valid)
            if self.mixer == "mamba2":
                return Mamba2Mixer(cfg, name="mamba")(x, decode, new_span, row_valid)
            if self.mixer == "conv":
                return ShortConvMixer(cfg, name="conv")(x, decode, new_span)
            if cfg.stateful:
                # beside layers that keep a recurrent state, attention is told the window's new tokens too: an
                # overlapped head's hidden states come out of layers that did not advance, so its rows are kept
                return attn_cls(cfg, name="attn")(x, positions, decode, new_span)
            return attn_cls(cfg, name="attn")(x, positions, decode)

        def mlp(x):
            if self.routed:
                return RoutedFFN(cfg, name="mlp")(x, row_valid)
            return LlamaMLP(cfg, name="mlp")(x)

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.norm_plus_one, name=name)

        if cfg.norm_after:
            # OLMo2 convention: normalize each sublayer's OUTPUT before the
            # residual add (no input norms); HF key post_attention_layernorm
            # maps to post_attn_norm, post_feedforward_layernorm to
            # post_ffn_norm
            hidden = hidden + norm("post_attn_norm")(attn(hidden))
            return hidden + norm("post_ffn_norm")(mlp(hidden))
        if cfg.sandwich_norm:
            # Gemma2 convention: pre- AND post-norm around each sublayer
            hidden = hidden + norm("post_attn_norm")(attn(norm("input_norm")(hidden)))
            return hidden + norm("post_ffn_norm")(mlp(norm("pre_ffn_norm")(hidden)))
        if cfg.residual_multiplier is not None:  # Granite: both branches scaled before the residual add
            by = jnp.asarray(cfg.residual_multiplier, hidden.dtype)
            hidden = hidden + attn(norm("input_norm")(hidden)) * by
            return hidden + mlp(norm("post_attn_norm")(hidden)) * by
        if cfg.fp32_skip_add:  # EvaByte: each residual sum in float32, rounded once to the stream's type
            def add(x, branch):
                return (x.astype(jnp.float32) + branch.astype(jnp.float32)).astype(x.dtype)

            hidden = add(hidden, attn(norm("input_norm")(hidden)))
            return add(hidden, mlp(norm("post_attn_norm")(hidden)))
        hidden = hidden + attn(norm("input_norm")(hidden))
        return hidden + mlp(norm("post_attn_norm")(hidden))


class _ScanLayer(nn.Module):
    """scan-compatible wrapper: carry-in/carry-out signature. With a
    ``layer`` index the carry is ``(hidden, key_pools, value_pools)``: the
    paged pools of all layers, which this layer updates in place."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, carry, positions, decode: bool = False, layer=None):
        if layer is None:
            return LlamaLayer(self.config, name="block")(carry, positions, decode), None
        from ..ops.paged_kv import layer_view

        hidden, key_pools, value_pools = carry
        with layer_view(key_pools, value_pools, layer) as view:
            hidden = LlamaLayer(self.config, name="block")(hidden, positions, decode)
        return (hidden, view.key_pool, view.value_pool), None


def rows_at(hidden, at):
    """``hidden[:, at]`` as ``[batch, n, width]`` for int32 positions ``at`` along the sequence (``[n]`` or a
    scalar, clipped to it): a masked sum over the sequence, not a gather. Each sum has one row in it, so it is exact,
    and a row nobody asked for may hold anything (it is left out, not multiplied by 0). A gather or a dynamic slice
    as the last reader of the residual stream has the TPU compiler lay a prefill's whole program out again (EvaByte's
    4096 bucket: 0.48 -> 0.73 GiB of temporaries); a reduction reads it in the layout the head's product did
    (PERF.md 6, PR 45)."""
    at = jnp.clip(jnp.atleast_1d(at), 0, hidden.shape[1] - 1)
    here = jnp.arange(hidden.shape[1])[None, :, None] == at[:, None, None]  # [n, sequence, 1]
    return jnp.where(here, hidden[:, None], 0).sum(2)


class LlamaModel(nn.Module):
    """The zoo's decoder: embedding, the layers ``config`` describes, the final
    norm and the float32 output head; logits ``[batch, sequence, vocab]``.
    ``logits_at`` (int32 positions along the sequence, ``[n]`` or a scalar):
    the caller reads these positions' logits alone and gets ``[batch, n,
    vocab]``; the norm and the head run on those rows of the last layer's
    output, every layer (and a cache the call writes) on the whole sequence.
    None, the default: every position."""

    config: LlamaConfig

    @nn.compact
    def __call__(
        self, input_ids, positions=None, decode: bool = False, new_span=None, row_valid=None, logits_at=None
    ):
        cfg = self.config
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens")
        hidden = embed(input_ids)
        if cfg.scale_embeddings:
            # Gemma multiplies embeddings by sqrt(hidden); the constant is
            # cast to the stream dtype FIRST (HF casts to bf16 there, and
            # matching the rounding keeps fp32 parity tests exact)
            hidden = hidden * jnp.asarray(cfg.hidden_size**0.5, hidden.dtype)
        if cfg.embedding_multiplier is not None:
            hidden = hidden * jnp.asarray(cfg.embedding_multiplier, hidden.dtype)  # Granite
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(input_ids.shape[-1]), input_ids.shape)
        # constrain activations onto the mesh (seq axis = Megatron-SP)
        from ..parallel.sharding import maybe_shard

        hidden = maybe_shard(hidden, ACTIVATION_SPEC)

        if cfg.scan_layers and cfg.stateful:
            raise NotImplementedError(
                "layers with a recurrent state (Mamba, Mamba-2, gated short convolution) are built with "
                "scan_layers=False: a scanned block shares one mixer across layers, and the carried pool "
                "stack holds K/V pools only, no ssm_state or conv_state"
            )
        if cfg.layer_types is not None and cfg.scan_layers:
            raise ValueError(
                "layer_types (per-layer sliding/full attention, Gemma2) requires "
                "scan_layers=False — a scanned block shares one static config"
            )
        if cfg.layer_types is not None and len(cfg.layer_types) != cfg.num_hidden_layers:
            raise ValueError(
                f"layer_types has {len(cfg.layer_types)} entries for "
                f"{cfg.num_hidden_layers} layers"
            )
        routed = cfg.n_routed_experts is not None
        if cfg.scan_layers and (routed or cfg.kv_lora_rank is not None):
            raise NotImplementedError(
                "latent attention and routed experts are built with scan_layers=False: a leading dense "
                "layer differs from the expert layers, and the carried pool stack holds K/V pools only"
            )
        if cfg.attention_class is not None and (cfg.scan_layers or cfg.kv_lora_rank is not None or cfg.stateful):
            raise NotImplementedError(
                "EVA attention (attention_class) is built with scan_layers=False, beside neither latent attention "
                "nor a stateful mixer: the carried pool stack has no summary table"
            )
        if cfg.stateful and cfg.kv_lora_rank is not None:
            raise NotImplementedError(
                "layers with a recurrent state (Mamba, gated short convolution) beside latent attention "
                "(kv_lora_rank): no configuration runs them together"
            )
        if cfg.scan_layers:
            # A paged decode step carries the pools of all layers through the
            # loop (scanned over like the rest of the cache, every layer would
            # slice its pool out of the stack and write it back: two passes
            # over the whole pool a token); block tables and frontiers are
            # small and stay scanned.
            pools = None
            if decode:
                from ..ops.paged_kv import declare_pool_stack

                head_dim = cfg.head_dim or cfg.hidden_size // cfg.num_attention_heads
                pools = declare_pool_stack(
                    self, cfg.num_hidden_layers, cfg.num_key_value_heads, head_dim, hidden.dtype
                )
            layer_cls = nn.remat(_ScanLayer, prevent_cse=False, static_argnums=(3,)) if cfg.remat else _ScanLayer
            scanned = nn.scan(
                layer_cls,
                variable_axes={"params": 0, "cache": 0, "fp8": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast, nn.broadcast) + (() if pools is None else (0,)),
                length=cfg.num_hidden_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )
            if pools is None:
                hidden, _ = scanned(cfg, name="layers")(hidden, positions, decode)
            else:
                key_pools, value_pools = pools
                (hidden, key_pools.value, value_pools.value), _ = scanned(cfg, name="layers")(
                    (hidden, key_pools.value, value_pools.value), positions, decode,
                    jnp.arange(cfg.num_hidden_layers),
                )
        else:
            # the first ``first_k_dense_replace`` layers of a config with routed experts keep the dense MLP
            n_lead = cfg.first_k_dense_replace if routed else 0
            layer_cls = nn.remat(LlamaLayer, prevent_cse=False, static_argnums=(3,)) if cfg.remat else LlamaLayer
            for i in range(cfg.num_hidden_layers):
                lcfg = cfg
                if cfg.layer_types is not None:
                    # Gemma2/3 alternating local/global attention: the band
                    # only applies on "sliding_attention" layers, which in
                    # Gemma3 also rotate with the LOCAL theta and no scaling
                    # (a "conv" layer has no attention: ``mixer_kind`` below)
                    windowed = cfg.layer_types[i] == "sliding_attention"
                    overrides = {"sliding_window": cfg.sliding_window if windowed else None}
                    if windowed and cfg.rope_local_theta is not None:
                        overrides["rope_theta"] = cfg.rope_local_theta
                        overrides["rope_scaling"] = None
                    overrides.update(cfg.layer_overrides(i))
                    lcfg = dataclasses.replace(cfg, **overrides)
                sparse = i >= n_lead if cfg.mlp_layer_types is None else cfg.mlp_layer_types[i] == "sparse"
                hidden = layer_cls(lcfg, routed and sparse, cfg.mixer_kind(i), name=f"layer_{i}")(
                    hidden, positions, decode, new_span, row_valid
                )
        if logits_at is not None:
            # the caller reads these positions' logits alone (a bucket's prefill keeps one row): the norm and the
            # head run on their rows, [batch, n, hidden]. Every layer above saw the whole sequence, so the cache
            # is that of the whole call
            hidden = rows_at(hidden, logits_at)
        hidden = RMSNorm(cfg.rms_norm_eps, cfg.norm_plus_one, name="final_norm")(hidden)
        if cfg.tie_word_embeddings:
            # true weight tying: reuse the embedding table (no lm_head
            # param at all), matching HF tied-head semantics under
            # fine-tuning and halving the head+table HBM
            logits = hidden.astype(jnp.float32) @ embed.embedding.astype(jnp.float32).T
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head", dtype=jnp.float32)(hidden)
        if cfg.logits_scaling is not None:
            logits = logits / cfg.logits_scaling  # Granite
        if cfg.final_logit_softcap is not None:
            from ..ops.attention import softcap

            logits = softcap(logits, cfg.final_logit_softcap)
        return logits


def _wrap_llama(module: LlamaModel, params, config: LlamaConfig, state=None) -> Model:
    def apply_fn(
        p, input_ids, positions=None, decode=False, cache=None, state=None, new_span=None, row_valid=None,
        logits_at=None,
    ):
        """decode=True threads the KV cache: pass ``cache`` (or None to
        initialise) and receive ``(logits, new_cache)``. ``state`` threads
        non-param collections (the fp8 amax histories): returns
        ``(logits, new_state)``. ``new_span`` ``(lo, hi)``: which of the
        window's tokens are new and real (a bucket's right pad and a chunk
        window's overlapped head are not); only a model whose layers keep
        a recurrent state reads it, and None means every token.
        ``row_valid`` (bool, the shape of ``input_ids``): the tokens that
        count, a decode tick's slots in which a request decodes; the routed
        experts read it (the others' rows reach none of them) and a
        state-space layer's step kernel (the others' states are neither
        read nor written); None means every token. ``logits_at`` (int32
        positions along the sequence, ``[n]`` or a scalar): the caller
        reads the logits of these positions alone and gets
        ``[batch, n, vocab]``, the final norm and the head having run on
        those rows of the last layer's output; the cache is the whole
        call's. None means every position."""
        if decode:
            variables = {"params": p, **(state or {})}
            if cache is not None:
                variables["cache"] = cache
            # non-param collections (fp8 amax histories) must be mutable
            # too — their per-step updates are discarded during decode
            mutable = ["cache", *(state or {})]
            from ..ops.moe import EXPERT_LOAD, requested_expert_load

            # routed experts' counts, for a caller inside ``ops.moe.expert_load_counts()``
            loads = requested_expert_load()
            if loads is not None:
                mutable.append(EXPERT_LOAD)
            logits, mutated = module.apply(
                variables, input_ids, positions, True, new_span, row_valid, logits_at, mutable=mutable
            )
            if loads is not None:
                loads.extend(jax.tree_util.tree_leaves(mutated.get(EXPERT_LOAD, {})))
            return logits, mutated["cache"]
        if state:
            variables = {"params": p, **state}
            logits, new_state = module.apply(
                variables, input_ids, positions, logits_at=logits_at, mutable=list(state.keys())
            )
            return logits, dict(new_state)
        return module.apply({"params": p}, input_ids, positions, logits_at=logits_at)

    model = Model(apply_fn, params, sharding_rules=LLAMA_SHARDING_RULES, name="llama")
    model.config = config
    model.module = module
    model.state = state
    return model


def create_llama_model(
    config: Optional[LlamaConfig] = None, seed: int = 0, seq_len: int = 128, dtype=None
) -> Model:
    """Seeded random-weight model. The init is ONE jitted program on the
    default device (an eager ``module.init`` runs the whole forward
    op-by-op, which a full-width config cannot afford on a chip);
    ``dtype`` casts the float params inside that program, so a bf16 model
    never holds its float32 copy."""
    config = config or LlamaConfig.tiny()
    module = LlamaModel(config)

    def init(key):
        variables = module.init(key, jnp.zeros((2, seq_len), jnp.int32))
        if dtype is not None:
            variables["params"] = jax.tree.map(
                lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
                variables["params"],
            )
        return variables

    variables = jax.jit(init)(jax.random.key(seed))
    params = variables["params"]
    state = {k: v for k, v in variables.items() if k != "params"} or None
    return _wrap_llama(module, params, config, state=state)


def causal_lm_loss_state(params, state, batch, apply_fn):
    """:func:`causal_lm_loss` for stateful models (fp8 delayed scaling):
    ``build_train_step(has_state=True)`` contract — returns
    ``(loss, new_state)``."""
    logits, new_state = apply_fn(params, batch["input_ids"], state=state)
    return next_token_cross_entropy(logits, batch), new_state


_PROJ_RE = re.compile(r"^(q|k|v|o|gate|up|down)_proj$")


def quantize_llama_model(model: Model, qconfig=None) -> Model:
    """Weight-only quantize every block projection of a llama :class:`Model`
    into the in-scan :class:`~accelerate_tpu.ops.qdense.QuantDense` layout.

    Unlike the generic wrap-and-dequantize fallback (which materialises the
    full-precision stack outside the layer scan), the packed codes here ARE
    the params, so per-decode-step HBM traffic is the int8/int4 bytes —
    the TPU analogue of the reference's bnb layer replacement
    (reference: src/accelerate/utils/bnb.py:276-373).
    """
    from ..utils.quantization import QuantizationConfig, quantize

    qcfg = qconfig or QuantizationConfig()
    if model.config.quant_method is not None:
        # re-quantizing would find no 'kernel' leaves, rewrite quant_method,
        # and silently reinterpret the packed codes under the new decoder
        raise ValueError(
            f"model is already quantized ({model.config.quant_method}); "
            "quantize the original float model instead"
        )
    new_cfg = dataclasses.replace(model.config, quant_method=qcfg.method, quant_group_size=qcfg.group_size)

    def convert(tree):
        if not hasattr(tree, "items"):
            return tree
        out = {}
        for k, v in tree.items():
            if hasattr(v, "items") and _PROJ_RE.match(k) and "kernel" in v:
                qt = quantize(jnp.asarray(v["kernel"]), qcfg)
                out[k] = {"qdata": qt.data, "qscale": qt.scale}
            else:
                out[k] = convert(v)
        return out

    return _wrap_llama(LlamaModel(new_cfg), convert(model.params), new_cfg)


def causal_lm_loss(params, batch, apply_fn):
    """Next-token cross entropy; labels = input shifted left, padding via
    ``loss_mask``. When labels are auto-derived, the final position (whose
    target would be fabricated) is masked out."""
    return next_token_cross_entropy(apply_fn(params, batch["input_ids"]), batch)


def next_token_cross_entropy(logits, batch):
    """The CE part of :func:`causal_lm_loss`, for callers that already have
    logits (e.g. MoE losses that need the same forward's aux outputs)."""
    mask = batch.get("loss_mask")
    if "labels" in batch:
        labels = batch["labels"]
    else:
        labels = jnp.pad(batch["input_ids"][:, 1:], ((0, 0), (0, 1)))
        last_pos = jnp.zeros(labels.shape, bool).at[:, -1].set(True)
        mask = jnp.where(last_pos, 0.0, 1.0 if mask is None else mask)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
