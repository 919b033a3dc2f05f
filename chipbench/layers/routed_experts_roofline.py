from chipbench.layer_tools import _peaks
from chipbench.layers import _decode_programs

EXPERT_PRODUCTS = "ragged-dot"  # the grouped products of ops/moe.py dropless_moe_ffn, as XLA names them on the device


def read(observed):
    """Kernels: the bytes the routed experts' three products must move in the traced decode ticks (the
    weights of the experts that got a token, as the program counted them, and each token-expert pair's
    activations) over 819 GB/s, over the device seconds of those products inside the ticks' decode
    programs. ``None`` where the program carries no count or the device ran no such product."""
    cfg, family = observed["config"], observed["family"]
    ticks = [t for t in _decode_programs.decode_ticks(observed) if t["stats"].get("experts_touched") and t["ops"] is not None]
    seconds = sum(_decode_programs.seconds_of(t, EXPERT_PRODUCTS) for t in ticks)
    if not ticks or not seconds:
        return None
    # every slot of the static tick routes a token, a decoding one or not: pairs = slots x k a layer a step
    slots, k = observed["config"]["bench"]["serving"]["num_slots"], cfg["num_experts_per_tok"]
    need = sum(family.expert_products_bytes(
        cfg, t["stats"]["experts_touched"], slots * k * family.expert_layers(cfg) * t["dispatch"]["tick_block"]) for t in ticks)
    return 100.0 * need / _peaks(observed)["hbm_bytes_per_s"] / seconds
