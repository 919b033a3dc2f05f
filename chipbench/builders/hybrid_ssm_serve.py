"""``hybrid_ssm_serve``: ``ServingEngine`` over the llama core with state-space layers beside
attention, at the configuration's widths: the deployment the configuration file states. The two
attention layers' K/V live in paged pools, every state-space layer's recurrent state is one row a
slot of the same cache tree. Layers are unrolled, so every tensor of the family's ``spec`` is one
leaf of the program's tree; the recurrence's constants go through the family's own map
(``reference/hybrid_ssm.py`` ``ssm_constants``), the same one its reference reads them through."""

from __future__ import annotations

from chipbench.builders._server import Server
from chipbench.builders._tree import check_same_shapes, to_tree
from chipbench.builders.llama_core_train import abstract_params
from chipbench.reference import hybrid_ssm as family

_COMMON = [("norm_mixer", "input_norm|scale"), ("norm_mlp", "post_attn_norm|scale"), ("w_gate", "mlp|gate_proj|kernel"),
           ("w_up", "mlp|up_proj|kernel"), ("w_down", "mlp|down_proj|kernel")]
_ATTENTION = [("wq", "attn|q_proj|kernel"), ("wk", "attn|k_proj|kernel"), ("wv", "attn|v_proj|kernel"),
              ("wo", "attn|o_proj|kernel")]
_MAMBA = [
    ("in_proj", "mamba|in_proj|kernel"), ("conv_w", "mamba|conv_kernel"), ("conv_b", "mamba|conv_bias"),
    ("x_proj", "mamba|x_proj|kernel"), ("norm_dt", "mamba|dt_norm|scale"), ("norm_b", "mamba|b_norm|scale"),
    ("norm_c", "mamba|c_norm|scale"), ("dt_proj", "mamba|dt_proj"), ("dt_bias", "mamba|dt_bias"),
    ("A_log", "mamba|A_log"), ("d_skip", "mamba|D"), ("out_proj", "mamba|out_proj|kernel"),
]


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
            out[family.name(i, "dt_bias")], out[family.name(i, "A_log")] = family.ssm_constants(
                flat[family.name(i, "dt_bias_raw")], flat[family.name(i, "a_raw")])
    return out


def core_config(config: dict):
    try:
        from accelerate_tpu.models.jamba import JambaConfig
    except ImportError as e:  # a program from before state-space layers were on the core
        raise SystemExit(f"chipbench: the builder hybrid_ssm_serve cannot build this family: {e}")
    fields = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings", "rms_norm_eps", "tie_word_embeddings", "attn_layer_period",
              "attn_layer_offset", "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "mamba_conv_bias",
              "mamba_proj_bias", "num_experts", "num_experts_per_tok", "expert_layer_period", "expert_layer_offset")
    return JambaConfig(**{k: config[k] for k in fields}, scan_layers=False, remat=False)


def build(config: dict, traffic: dict, seed: int, make_weights) -> Server:
    cfg = core_config(config)  # first: a program without the family fails here, before any weights are made
    from accelerate_tpu.models.llama import _wrap_llama
    from accelerate_tpu.serving import ServingEngine

    module, shapes = abstract_params(cfg)
    tree = to_tree(with_constants(make_weights(), config), table(config), cfg.num_hidden_layers)
    check_same_shapes(tree, shapes)
    s = config["bench"]["serving"]
    engine = ServingEngine(
        _wrap_llama(module, tree, cfg), num_slots=s["num_slots"], prompt_buckets=tuple(s["prompt_buckets"]),
        max_len=s["max_len"], paged_block_size=s["paged_block_size"], pool_blocks=s["pool_blocks"], seed=seed & 0x7FFFFFFF,
    )
    return Server(engine, config)
