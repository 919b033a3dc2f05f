from chipbench.layers import _decode_programs


def read(observed):
    """Kernels: the rows one grid step of the grouped kernel multiplies by one expert's matrices: the
    token-expert pairs of a traced decode tick (slots x ``num_experts_per_tok`` x expert layers x the
    tick's steps, as ``routed_experts_roofline`` reckons them: every slot of the static tick routes a
    token) over its ``expert_tile_visits``, the (expert, row tile) visits of one grouped product as the
    program counted them in ``engine.tick.done``; mean over the traced decode ticks. What more slots, or
    several prompts a prefill, would raise toward the MXU's ridge (about 240 rows on a v5e). ``None``
    where the program carries no such count."""
    cfg, family = observed["config"], observed["family"]
    ticks = [t for t in _decode_programs.decode_ticks(observed) if t["stats"].get("expert_tile_visits")]
    if not ticks:
        return None
    slots, k = cfg["bench"]["serving"]["num_slots"], cfg["num_experts_per_tok"]
    per = [slots * k * family.expert_layers(cfg) * t["dispatch"]["tick_block"] / t["stats"]["expert_tile_visits"] for t in ticks]
    return sum(per) / len(per)
