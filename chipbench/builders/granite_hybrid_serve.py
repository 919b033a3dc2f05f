"""``granite_hybrid_serve``: ``ServingEngine`` over the llama core with Mamba-2 layers beside attention,
routed experts with a shared one in every layer and Granite's multipliers, at the configuration's widths
and **its share of the experts**: the deployment the configuration file states. The attention layers' K/V
live in a paged pool, every state-space layer's recurrent state (``[mamba_d_state, d_inner]`` float32 and
the convolution's carried inputs) is one row a slot of the same cache tree. Layers are unrolled, so every
tensor of the family's ``spec`` is one leaf of the program's tree; the recurrence's constants go through
the family's own map (``reference/granite_hybrid.py`` ``ssd_constants``), the same one its reference reads
them through. The program is told the share (``expert_shares``, ``expert_share``): its router keeps
``router_experts`` columns, its expert tensors are ``[num_local_experts, ..]``."""

from __future__ import annotations

from chipbench.builders._server import Server
from chipbench.builders._tree import check_same_shapes, to_tree
from chipbench.builders.llama_core_train import abstract_params
from chipbench.reference import granite_hybrid as family

try:
    from accelerate_tpu.models.granitemoehybrid import GraniteMoeHybridConfig
except ImportError as e:  # a program from before the family was on the core: at once, before any weights are made
    raise SystemExit(f"chipbench: the builder granite_hybrid_serve cannot build this family: {e}")

_COMMON = [("norm_mixer", "input_norm|scale"), ("norm_ffn", "post_attn_norm|scale"), ("router", "mlp|router/kernel"),
           ("experts_gate", "mlp|experts/gate_proj"), ("experts_up", "mlp|experts/up_proj"), ("experts_down", "mlp|experts/down_proj"),
           ("shared_gate", "mlp|shared_experts|gate_proj|kernel"), ("shared_up", "mlp|shared_experts|up_proj|kernel"),
           ("shared_down", "mlp|shared_experts|down_proj|kernel")]
_ATTENTION = [("wq", "attn|q_proj|kernel"), ("wk", "attn|k_proj|kernel"), ("wv", "attn|v_proj|kernel"),
              ("wo", "attn|o_proj|kernel")]
_MAMBA = [("in_proj", "mamba|in_proj|kernel"), ("conv_w", "mamba|conv_kernel"), ("conv_b", "mamba|conv_bias"),
          ("dt_bias", "mamba|dt_bias"), ("A_log", "mamba|A_log"), ("d_skip", "mamba|D"), ("norm_gate", "mamba|norm|scale"),
          ("out_proj", "mamba|out_proj|kernel")]


def table(config: dict) -> list:
    rows = [("embed", "embed_tokens|embedding", False), ("norm_final", "final_norm|scale", False)]
    for i in range(config["num_hidden_layers"]):
        kind = _ATTENTION if family.is_attention(config, i) else _MAMBA
        rows += [(family.name(i, name), f"layer_{i}|{path}", False) for name, path in _COMMON + kind]
    return rows


def with_constants(flat: dict, config: dict) -> dict:
    """The family's tensors, and beside each state-space layer's raw draws the ``dt_bias`` and ``A_log``
    the program holds: float32, through the family's map."""
    out = dict(flat)
    for i in range(config["num_hidden_layers"]):
        if not family.is_attention(config, i):
            out[family.name(i, "dt_bias")], out[family.name(i, "A_log")] = family.ssd_constants(
                flat[family.name(i, "dt_bias_raw")], flat[family.name(i, "a_raw")])
    return out


def core_config(config: dict):
    fields = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings", "rms_norm_eps", "tie_word_embeddings", "mamba_d_state",
              "mamba_d_conv", "mamba_expand", "mamba_n_heads", "mamba_d_head", "mamba_n_groups", "mamba_chunk_size",
              "mamba_conv_bias", "mamba_proj_bias", "num_experts_per_tok", "shared_intermediate_size", "embedding_multiplier",
              "residual_multiplier", "attention_multiplier", "logits_scaling", "position_embedding_type")
    _, held, total = family.held_experts(config)
    return GraniteMoeHybridConfig(
        **{k: config[k] for k in fields}, layer_types=tuple(config["layer_types"]), num_local_experts=total,
        expert_shares=total // held, expert_share=config.get("expert_share", 0), scan_layers=False, remat=False)


def build(config: dict, traffic: dict, seed: int, make_weights) -> Server:
    from accelerate_tpu.models.llama import _wrap_llama
    from accelerate_tpu.serving import ServingEngine

    cfg = core_config(config)
    module, shapes = abstract_params(cfg)
    tree = to_tree(with_constants(make_weights(), config), table(config), cfg.num_hidden_layers)
    check_same_shapes(tree, shapes)
    s = config["bench"]["serving"]
    engine = ServingEngine(
        _wrap_llama(module, tree, cfg), num_slots=s["num_slots"], prompt_buckets=tuple(s["prompt_buckets"]),
        max_len=s["max_len"], paged_block_size=s["paged_block_size"], pool_blocks=s["pool_blocks"], seed=seed & 0x7FFFFFFF,
    )
    return Server(engine, config)
