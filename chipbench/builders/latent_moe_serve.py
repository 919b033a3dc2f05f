"""``latent_moe_serve``: ``ServingEngine`` with the paged latent cache over the llama core with
latent attention and routed experts, at the configuration's widths: the deployment the
configuration file states. Layers are unrolled (``scan_layers=False``), so every tensor of the
family's ``spec`` is one leaf of the program's tree and nothing is stacked or copied."""

from __future__ import annotations

from chipbench.builders._server import Server
from chipbench.builders._tree import check_same_shapes, to_tree
from chipbench.builders.llama_core_train import abstract_params

_ATTENTION = [
    ("wq_a", "attn|q_a_proj|kernel"), ("norm_q", "attn|q_a_norm|scale"), ("wq_b", "attn|q_b_proj|kernel"),
    ("wkv_a", "attn|kv_a_proj|kernel"), ("norm_kv", "attn|kv_a_norm|scale"), ("wkv_b", "attn|kv_b_proj"),
    ("wo", "attn|o_proj|kernel"), ("norm_attn", "input_norm|scale"), ("norm_mlp", "post_attn_norm|scale"),
]
_DENSE = [("w_gate", "mlp|gate_proj|kernel"), ("w_up", "mlp|up_proj|kernel"), ("w_down", "mlp|down_proj|kernel")]
_ROUTED = [
    ("router", "mlp|router/kernel"), ("router_bias", "mlp|router/e_score_correction_bias"),
    ("experts_gate", "mlp|experts/gate_proj"), ("experts_up", "mlp|experts/up_proj"), ("experts_down", "mlp|experts/down_proj"),
    ("shared_gate", "mlp|shared_experts|gate_proj|kernel"), ("shared_up", "mlp|shared_experts|up_proj|kernel"),
    ("shared_down", "mlp|shared_experts|down_proj|kernel"),
]


def table(config: dict) -> list:
    rows = [("embed", "embed_tokens|embedding", False), ("norm_final", "final_norm|scale", False), ("lm_head", "lm_head|kernel", False)]
    for i in range(config["num_hidden_layers"]):
        kind = _ROUTED if i >= config["first_k_dense_replace"] else _DENSE
        rows += [(f"L{i:02d}.{name}", f"layer_{i}|{path}", False) for name, path in _ATTENTION + kind]
    return rows


def core_config(config: dict):
    import dataclasses

    from accelerate_tpu.models.llama import LlamaConfig

    fields = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings", "rms_norm_eps", "rope_theta", "rope_scaling",
              "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
              "num_experts_per_tok", "moe_intermediate_size", "n_shared_experts", "first_k_dense_replace",
              "routed_scaling_factor", "scoring_func", "norm_topk_prob")
    missing = sorted(set(fields) - {f.name for f in dataclasses.fields(LlamaConfig)})
    if missing:  # a program from before latent attention and routed experts were on the core
        raise SystemExit(f"chipbench: the builder latent_moe_serve cannot build this family: the program's LlamaConfig has no {', '.join(missing)}")
    return LlamaConfig(**{k: config[k] for k in fields}, scan_layers=False, remat=False)


def build(config: dict, traffic: dict, seed: int, make_weights) -> Server:
    from accelerate_tpu.models.llama import _wrap_llama
    from accelerate_tpu.serving import ServingEngine

    cfg = core_config(config)
    module, shapes = abstract_params(cfg)
    tree = to_tree(make_weights(), table(config), cfg.num_hidden_layers)
    check_same_shapes(tree, shapes)
    s = config["bench"]["serving"]
    engine = ServingEngine(
        _wrap_llama(module, tree, cfg), num_slots=s["num_slots"], prompt_buckets=tuple(s["prompt_buckets"]),
        max_len=s["max_len"], paged_block_size=s["paged_block_size"], pool_blocks=s["pool_blocks"], seed=seed & 0x7FFFFFFF,
    )
    return Server(engine, config)
