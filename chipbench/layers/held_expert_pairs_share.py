from chipbench.layers import _decode_programs


def read(observed):
    """ops/moe routing: of the token-expert pairs the decoding slots routed (slots that decode x
    ``num_experts_per_tok`` x expert layers x the tick's steps, over all the router's experts), the share
    that reached an expert held here and that the grouped products multiplied: the ``expert_pairs`` count of
    ``engine.tick.done``; mean over the traced decode ticks. Under a share of the experts (one chip of an
    expert-parallel pair) about that share at seeded weights; 100 says that no share is applied, 0 that
    nothing is held. ``None`` where the program carries no such count."""
    cfg, family = observed["config"], observed["family"]
    ticks = [t for t in _decode_programs.decode_ticks(observed) if t["stats"].get("expert_pairs") and t["dispatch"].get("decoding")]
    if not ticks:
        return None
    k, layers = cfg["num_experts_per_tok"], family.expert_layers(cfg)
    per = [t["stats"]["expert_pairs"] / (t["dispatch"]["decoding"] * k * layers * t["dispatch"]["tick_block"]) for t in ticks]
    return 100.0 * sum(per) / len(per)
