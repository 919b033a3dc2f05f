"""``llama_core_train``: the llama core at the configuration's widths through
``Accelerator`` on the configuration's mesh, AdamW, ``causal_lm_loss``."""

from __future__ import annotations

from chipbench.builders._trainer import Trainer
from chipbench.builders._tree import check_same_shapes, reset_accelerator_state, to_tree

_BLOCK = "layers|block|"
TABLE = [
    ("embed", "embed_tokens|embedding", False),
    ("wq", _BLOCK + "attn|q_proj|kernel", False), ("wk", _BLOCK + "attn|k_proj|kernel", False),
    ("wv", _BLOCK + "attn|v_proj|kernel", False), ("wo", _BLOCK + "attn|o_proj|kernel", False),
    ("w_gate", _BLOCK + "mlp|gate_proj|kernel", False), ("w_up", _BLOCK + "mlp|up_proj|kernel", False),
    ("w_down", _BLOCK + "mlp|down_proj|kernel", False),
    ("norm_attn", _BLOCK + "input_norm|scale", False), ("norm_mlp", _BLOCK + "post_attn_norm|scale", False),
    ("norm_final", "final_norm|scale", False), ("lm_head", "lm_head|kernel", False),
]


def mistral_config(config: dict):
    from accelerate_tpu.models import MistralConfig

    fields = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings", "rms_norm_eps", "rope_theta", "sliding_window")
    return MistralConfig(**{k: config[k] for k in fields})


def abstract_params(cfg):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.llama import LlamaModel

    module = LlamaModel(cfg)
    shapes = jax.eval_shape(lambda k: module.init(k, jnp.zeros((2, 8), jnp.int32)), jax.random.key(0))["params"]
    return module, shapes


def build(config: dict, traffic: dict, seed: int, make_weights) -> Trainer:
    import jax
    import numpy as np
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import causal_lm_loss
    from accelerate_tpu.models.llama import _wrap_llama
    from accelerate_tpu.parallel.mesh import MeshConfig, batch_sharding
    from accelerate_tpu.parallel.sharding import infer_shardings
    from accelerate_tpu.utils import ParallelismPlugin

    from chipbench.builders._tree import _get

    bench = config["bench"]
    reset_accelerator_state()
    accelerator = Accelerator(
        mixed_precision="bf16", parallelism_plugin=ParallelismPlugin(mesh_config=MeshConfig(**bench["mesh"]))
    )
    cfg = mistral_config(config)
    module, shapes = abstract_params(cfg)
    model = _wrap_llama(module, shapes, cfg)
    shardings = infer_shardings(shapes, accelerator._sharding_rules_for(model), accelerator.mesh)
    tree = to_tree(make_weights({name: _get(shardings, path) for name, path, _ in TABLE}), TABLE, cfg.num_hidden_layers)
    check_same_shapes(tree, shapes)
    model.params = tree
    model = accelerator.prepare_model(model)
    opt = bench["optimizer"]
    accelerator.prepare_optimizer(
        optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"], weight_decay=opt["weight_decay"])
    )
    step = accelerator.build_train_step(lambda p, b: causal_lm_loss(p, b, model.apply_fn))

    batch, seq = traffic["batch"], traffic["seq"]
    rng = np.random.default_rng(seed)
    batches = [{"input_ids": rng.integers(5, cfg.vocab_size - 1, size=(batch, seq)).astype(np.int32)}
               for _ in range(traffic["distinct_batches"])]
    sharding = batch_sharding(accelerator.mesh)
    return Trainer(
        accelerator=accelerator, model=model, step=step, table=TABLE, layers=cfg.num_hidden_layers, b1=opt["b1"],
        batches=batches, device_batch=lambda b: jax.device_put(b, sharding), tokens_per_step=batch * seq,
        ref_batch=lambda b: b["input_ids"],
    )
