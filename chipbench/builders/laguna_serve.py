"""``laguna_serve``: ``ServingEngine`` over the llama core with full and window attention layers (query heads, a
rotary rule and a gate by layer), routed experts with a shared one in every layer but the first, at the
configuration's widths and **its share of the experts**: the deployment the configuration file states. Under
the paged layout the full layers' K/V live in one pool under ``block_table`` and the window layers' in a pool of
their own under a ring table a slot (``bench.serving`` ``pool_blocks`` / ``window_pool_blocks``). Layers are
unrolled, so every tensor of the family's ``spec`` is one leaf of the program's tree: the seeded arrays themselves,
no second copy. The three lists a layer may be the published ones whole: the first ``num_hidden_layers`` entries
count, in the program's configuration as in the family. The program is told the share (``expert_shares``,
``expert_share``): its router keeps ``router_experts`` columns, its expert tensors are ``[num_experts, ..]``."""

from __future__ import annotations

from chipbench.builders._server import Server
from chipbench.builders._tree import check_same_shapes, to_tree
from chipbench.builders.llama_core_train import abstract_params
from chipbench.reference import laguna as family

try:
    from accelerate_tpu.models.laguna import LagunaConfig
except ImportError as e:  # a program from before the family was on the core: at once, before any weights are made
    raise SystemExit(f"chipbench: the builder laguna_serve cannot build this family: {e}")

_COMMON = [("norm_attn", "input_norm|scale"), ("norm_ffn", "post_attn_norm|scale"), ("wq", "attn|q_proj|kernel"),
           ("wk", "attn|k_proj|kernel"), ("wv", "attn|v_proj|kernel"), ("wo", "attn|o_proj|kernel"), ("wg", "attn|g_proj|kernel"),
           ("norm_q", "attn|q_norm|scale"), ("norm_k", "attn|k_norm|scale")]
_DENSE = [("w_gate", "mlp|gate_proj|kernel"), ("w_up", "mlp|up_proj|kernel"), ("w_down", "mlp|down_proj|kernel")]
_SPARSE = [("router", "mlp|router/kernel"), ("router_bias", "mlp|router/e_score_correction_bias"),
           ("experts_gate", "mlp|experts/gate_proj"), ("experts_up", "mlp|experts/up_proj"), ("experts_down", "mlp|experts/down_proj"),
           ("shared_gate", "mlp|shared_experts|gate_proj|kernel"), ("shared_up", "mlp|shared_experts|up_proj|kernel"),
           ("shared_down", "mlp|shared_experts|down_proj|kernel")]


def table(config: dict) -> list:
    rows = [("embed", "embed_tokens|embedding", False), ("norm_final", "final_norm|scale", False), ("lm_head", "lm_head|kernel", False)]
    for i in range(config["num_hidden_layers"]):
        kind = _SPARSE if family.is_sparse(config, i) else _DENSE
        rows += [(family.name(i, name), f"layer_{i}|{path}", False) for name, path in _COMMON + kind]
    return rows


def core_config(config: dict):
    fields = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
              "head_dim", "max_position_embeddings", "rms_norm_eps", "tie_word_embeddings", "sliding_window", "num_experts_per_tok",
              "moe_intermediate_size", "shared_expert_intermediate_size", "moe_routed_scaling_factor",
              "moe_apply_router_weight_on_input", "gating")
    _, held, total = family.held_experts(config)
    rope = {kind: dict(config["rope_parameters"][kind]) for kind in (family.FULL, family.WINDOW)}
    return LagunaConfig(
        **{k: config[k] for k in fields}, layer_types=tuple(config["layer_types"]), mlp_layer_types=tuple(config["mlp_layer_types"]),
        num_attention_heads_per_layer=tuple(config["num_attention_heads_per_layer"]), rope_parameters=rope, num_experts=total,
        expert_shares=total // held, expert_share=config.get("expert_share", 0), remat=False)


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
        max_len=s["max_len"], paged_block_size=s["paged_block_size"], pool_blocks=s["pool_blocks"],
        window_pool_blocks=s["window_pool_blocks"], tick_block=s.get("tick_block", 8), seed=seed & 0x7FFFFFFF,
    )
    return Server(engine, config)
