from chipbench.layers import _decode_programs


def read(observed):
    """ops/moe routing: distinct experts that got a token, a layer a decode step, over the experts a
    layer has; mean over the traced decode ticks. From the ``experts_touched`` count of
    ``engine.tick.done`` (summed there over expert layers and the tick's steps). ``None`` where the
    program carries no such count."""
    cfg, family = observed["config"], observed["family"]
    ticks = [t for t in _decode_programs.decode_ticks(observed) if t["stats"].get("experts_touched")]
    if not ticks:
        return None
    per = [t["stats"]["experts_touched"] / (family.expert_layers(cfg) * t["dispatch"]["tick_block"]) for t in ticks]
    return 100.0 * sum(per) / len(per) / cfg["n_routed_experts"]
