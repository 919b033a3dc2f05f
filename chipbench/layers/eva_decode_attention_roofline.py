from chipbench.layers import _eva_ticks
from chipbench.peaks import peaks_for


def read(observed):
    """Kernels: the paged decode kernel under an aligned window. The bytes of the rows the kept steps read (the
    program's ``attn_rows_read``, NOT the contexts: a closed window leaves a sixteenth), keys and values, with the
    queries and outputs, every layer (the family's ``cache_bytes_per_decode_step``), over the chip's
    memory bandwidth, over the seconds of the device operations whose name is ``paged_decode_attention`` inside
    the traced ticks' decode programs. Steps a slot computed past its last kept token are in the seconds and not
    in the bytes: the share reads low by them. ``None`` where the program carries no such count or the trace names
    no such operation (a CPU's)."""
    ticks = [t for t in _eva_ticks.ticks(observed) if t["ops"]]
    seconds = sum(_eva_ticks.kernel_seconds(t) for t in ticks)
    if not seconds:
        return None
    need = sum(_eva_ticks.tick_bytes(observed, t) for t in ticks)
    return 100.0 * need / peaks_for(observed["device"]["kind"])["hbm_bytes_per_s"] / seconds
