from chipbench.layers import _eva_ticks
from chipbench.peaks import peaks_for


def read(observed):
    """Jitted programs: the decode tick under an aligned window. The bytes a tick must read (the weights once a
    step, and the rows its kept steps attended to, the program's ``attn_rows_read``: what ``decode_roofline_share``
    cannot know, which is handed the contexts' sum) over the chip's memory bandwidth, over the device seconds of
    the operations inside the tick's decode program; over the traced ticks that admitted nothing. ``None`` where
    the program carries no such count or the trace names no program (a CPU's)."""
    ticks = [t for t in _eva_ticks.ticks(observed) if t["ops"] and not t["stats"].get("admitted")]
    seconds = sum(d for t in ticks for _, d in t["ops"])
    if not seconds:
        return None
    cfg, family = observed["config"], observed["family"]
    need = sum(t["dispatch"]["tick_block"] * family.weight_bytes_per_decode_step(cfg, t["dispatch"]["decoding"])
               + _eva_ticks.tick_bytes(observed, t) for t in ticks)
    return 100.0 * need / peaks_for(observed["device"]["kind"])["hbm_bytes_per_s"] / seconds
