"""``lfm2_moe_serve``: ``ServingEngine`` over the llama core with gated short convolutions beside
attention and routed experts past the leading dense layers, at the configuration's widths: the
deployment the configuration file states. The attention layers' K/V live in paged pools, every
convolution layer's carried inputs are one ``conv_state`` row a slot of the same cache tree. Layers are
unrolled, so every tensor of the family's ``spec`` is one leaf of the program's tree and nothing is
stacked; the query and key projections and their norms' scales are re-paired on the way
(``re_paired``), as an importer of checkpoints re-pairs them: the family's reference turns the rotary
halves as published, the program's core turns adjacent pairs."""

from __future__ import annotations

from chipbench.builders._server import Server
from chipbench.builders._tree import check_same_shapes, to_tree
from chipbench.builders.llama_core_train import abstract_params
from chipbench.reference import lfm2_moe as family

try:
    from accelerate_tpu.models.lfm2_moe import Lfm2MoeConfig
except ImportError as e:  # a program from before the family was on the core: at once, before any weights are made
    raise SystemExit(f"chipbench: the builder lfm2_moe_serve cannot build this family: {e}")

_COMMON = [("norm_operator", "input_norm|scale"), ("norm_ffn", "post_attn_norm|scale")]
_CONV = [("in_proj", "conv|in_proj|kernel"), ("conv_w", "conv|conv_kernel"), ("out_proj", "conv|out_proj|kernel")]
_ATTENTION = [("wq", "attn|q_proj|kernel"), ("wk", "attn|k_proj|kernel"), ("wv", "attn|v_proj|kernel"),
              ("wo", "attn|o_proj|kernel"), ("norm_q", "attn|q_norm|scale"), ("norm_k", "attn|k_norm|scale")]
_DENSE = [("w_gate", "mlp|gate_proj|kernel"), ("w_up", "mlp|up_proj|kernel"), ("w_down", "mlp|down_proj|kernel")]
_ROUTED = [("router", "mlp|router/kernel"), ("router_bias", "mlp|router/e_score_correction_bias"),
           ("experts_gate", "mlp|experts/gate_proj"), ("experts_up", "mlp|experts/up_proj"),
           ("experts_down", "mlp|experts/down_proj")]


def table(config: dict) -> list:
    rows = [("embed", "embed_tokens|embedding", False), ("norm_final", "final_norm|scale", False)]
    for i in range(config["num_hidden_layers"]):
        kind = (_ATTENTION if family.is_attention(config, i) else _CONV) + (_ROUTED if family.is_routed(config, i) else _DENSE)
        rows += [(family.name(i, name), f"layer_{i}|{path}", False) for name, path in _COMMON + kind]
    return rows


def re_paired(flat: dict, config: dict) -> dict:
    """The family's tensors with each attention layer's ``wq``, ``wk``, ``norm_q`` and ``norm_k`` re-paired
    from the published half-split rotary layout to the core's adjacent pairs: within a head, column
    ``2j`` is the published ``j`` and column ``2j + 1`` the published ``j + D/2``."""
    d = family.head_dim(config)

    def pairs(x):  # [.., heads * D] -> the same, each head's halves interleaved
        return x.reshape(*x.shape[:-1], -1, 2, d // 2).swapaxes(-1, -2).reshape(x.shape)

    out = dict(flat)
    for i in range(config["num_hidden_layers"]):
        if family.is_attention(config, i):
            for tensor in ("wq", "wk", "norm_q", "norm_k"):
                out[family.name(i, tensor)] = pairs(flat[family.name(i, tensor)])
    return out


def core_config(config: dict):
    fields = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings", "norm_eps", "rope_theta", "conv_L_cache", "conv_bias",
              "num_dense_layers", "num_experts", "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob",
              "routed_scaling_factor", "use_expert_bias")
    return Lfm2MoeConfig(**{k: config[k] for k in fields}, layer_types=tuple(config["layer_types"]), scan_layers=False,
                         remat=False)


def build(config: dict, traffic: dict, seed: int, make_weights) -> Server:
    from accelerate_tpu.models.llama import _wrap_llama
    from accelerate_tpu.serving import ServingEngine

    cfg = core_config(config)
    module, shapes = abstract_params(cfg)
    tree = to_tree(re_paired(make_weights(), config), table(config), cfg.num_hidden_layers)
    check_same_shapes(tree, shapes)
    s = config["bench"]["serving"]
    engine = ServingEngine(
        _wrap_llama(module, tree, cfg), num_slots=s["num_slots"], prompt_buckets=tuple(s["prompt_buckets"]),
        max_len=s["max_len"], paged_block_size=s["paged_block_size"], pool_blocks=s["pool_blocks"], seed=seed & 0x7FFFFFFF,
    )
    return Server(engine, config)
