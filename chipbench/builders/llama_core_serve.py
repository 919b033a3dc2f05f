"""``llama_core_serve``: ``ServingEngine`` with the paged cache over the llama core at
the configuration's widths: the deployment the configuration file states."""

from __future__ import annotations

from chipbench.builders._server import Server
from chipbench.builders._tree import check_same_shapes, to_tree
from chipbench.builders.llama_core_train import TABLE, abstract_params, mistral_config


def build(config: dict, traffic: dict, seed: int, make_weights) -> Server:
    from accelerate_tpu.models.llama import _wrap_llama
    from accelerate_tpu.serving import ServingEngine

    cfg = mistral_config(config)
    module, shapes = abstract_params(cfg)
    tree = to_tree(make_weights(), TABLE, cfg.num_hidden_layers)
    check_same_shapes(tree, shapes)
    model = _wrap_llama(module, tree, cfg)
    s = config["bench"]["serving"]
    engine = ServingEngine(
        model, num_slots=s["num_slots"], prompt_buckets=tuple(s["prompt_buckets"]), max_len=s["max_len"],
        paged_block_size=s["paged_block_size"], pool_blocks=s["pool_blocks"], seed=seed & 0x7FFFFFFF,
    )
    return Server(engine, config)
