"""``llama_core_serve``: ``ServingEngine`` with the paged cache over the llama core at
the configuration's widths: the deployment the configuration file states."""

from __future__ import annotations

import gc

from ._tree import check_same_shapes, to_tree
from .llama_core_train import TABLE, abstract_params, mistral_config


class Server:
    """The engine behind the four calls the ``open_loop_rounds`` generator makes."""

    family = "mistral"

    def __init__(self, engine, config: dict):
        self.engine, self.config = engine, config
        self.tick_block = engine.tick_block

    def submit(self, prompt, new_tokens: int) -> int:
        return self.engine.submit(prompt, max_new_tokens=new_tokens)

    def step(self) -> None:
        self.engine.step()

    def tokens_so_far(self, uid: int):
        return self.engine.partial(uid)

    def finished(self, uid: int) -> bool:
        return self.engine.poll(uid) is not None

    def busy(self) -> bool:
        return bool(self.engine.queue) or self.engine.active_count > 0

    def counters(self) -> dict:
        m = self.engine.metrics
        return {"prefills": m.prefills, "queue_wait_ms": list(m.queue_wait_ms), "queue_len": len(self.engine.queue),
                "active": self.engine.active_count}

    def reset_counters(self) -> None:
        self.engine.metrics.queue_wait_ms.clear()

    def free(self) -> None:
        self.engine = None
        gc.collect()


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
