from chipbench.layers import _mixed_ticks
from chipbench.peaks import peaks_for


def read(observed):
    """Jitted programs: the decode tick of a model with full and window attention layers and a held share of routed
    experts. The bytes a tick must read (the weights outside the experts once a step, the held experts its steps
    touched, as the program counted them, and the rows by kind: the contexts on the full layers, the band on the
    window layers, which ``decode_roofline_share`` cannot know) over the chip's memory bandwidth, over the device
    seconds of the operations inside the tick's decode program; over the traced ticks that admitted nothing.
    ``None`` where the program carries no count by kind or the trace names no program (a CPU's)."""
    ticks = [t for t in _mixed_ticks.ticks(observed) if t["ops"] and not t["stats"].get("admitted")]
    seconds = sum(d for t in ticks for _, d in t["ops"])
    if not seconds:
        return None
    cfg, family = observed["config"], observed["family"]
    need = 0.0
    for t in ticks:
        block, decoding = t["dispatch"]["tick_block"], t["dispatch"]["decoding"]
        need += block * family.weight_bytes_per_decode_step(cfg, decoding, experts_touched=t["stats"].get("experts_touched", 0) / block)
        need += family.cache_bytes_per_decode_step(cfg, t["stats"]["context_rows"], t["stats"]["window_rows_read"], _mixed_ticks.steps(t))
    return 100.0 * need / peaks_for(observed["device"]["kind"])["hbm_bytes_per_s"] / seconds
