"""``evabyte_serve``: ``ServingEngine`` over the llama core with EVA attention in every layer, at the
configuration's widths: the deployment the configuration file states. A layer's K/V rows and the summaries
of its closed windows' chunks live in pages of one paged pool, under two tables a slot. Layers are unrolled,
so every tensor of the family's ``spec`` is one leaf of the program's tree: the seeded arrays themselves, no second copy."""

from __future__ import annotations

from chipbench.builders._server import Server
from chipbench.builders._tree import check_same_shapes, to_tree
from chipbench.builders.llama_core_train import abstract_params
from chipbench.reference import evabyte as family

try:
    from accelerate_tpu.models.evabyte import EvaByteConfig
except ImportError as e:  # a program from before the family was on the core: at once, before any weights are made
    raise SystemExit(f"chipbench: the builder evabyte_serve cannot build this family: {e}")

_LAYER = [("wq", "attn|q_proj|kernel"), ("wk", "attn|k_proj|kernel"), ("wv", "attn|v_proj|kernel"), ("wo", "attn|o_proj|kernel"),
          ("mu", "attn|adaptive_mu_k"), ("phi", "attn|adaptive_phi"), ("w_gate", "mlp|gate_proj|kernel"),
          ("w_up", "mlp|up_proj|kernel"), ("w_down", "mlp|down_proj|kernel"), ("norm_attn", "input_norm|scale"),
          ("norm_mlp", "post_attn_norm|scale")]


def table(config: dict) -> list:
    rows = [("embed", "embed_tokens|embedding", False), ("norm_final", "final_norm|scale", False), ("lm_head", "lm_head|kernel", False)]
    for i in range(config["num_hidden_layers"]):
        rows += [(family.name(i, tensor), f"layer_{i}|{path}", False) for tensor, path in _LAYER]
    return rows


def core_config(config: dict):
    fields = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
              "max_position_embeddings", "rms_norm_eps", "rope_theta", "window_size", "chunk_size", "norm_add_unit_offset",
              "fp32_skip_add", "fp32_logits", "tie_word_embeddings")
    return EvaByteConfig(**{k: config[k] for k in fields}, scan_layers=False, remat=False)


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
