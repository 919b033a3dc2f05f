from chipbench.layer_tools import _peaks
from chipbench.layers import _decode_programs

KERNEL = "latent_paged_decode"


def read(observed):
    """Kernels: the live latent rows once (keys and values both), the absorbed queries and the outputs of
    the decoding slots, over 819 GB/s, over the kernel's device seconds inside the traced ticks' decode
    programs. ``None`` where the device ran no such kernel."""
    cfg, family = observed["config"], observed["family"]
    ticks = [t for t in _decode_programs.decode_ticks(observed) if t["ops"] is not None]
    seconds = sum(_decode_programs.seconds_of(t, KERNEL) for t in ticks)
    if not seconds:
        return None
    need = 0.0
    for t in ticks:
        d = t["dispatch"]
        for step in range(d["tick_block"]):  # a slot grows by a token a step
            need += cfg["num_hidden_layers"] * family.latent_decode_bytes(cfg, d["live_tokens"] + d["decoding"] * step, d["decoding"])
    return 100.0 * need / _peaks(observed)["hbm_bytes_per_s"] / seconds
