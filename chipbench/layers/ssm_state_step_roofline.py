from chipbench.layer_tools import _peaks
from chipbench.layers import _decode_programs

KERNEL = "ssm_state_step"


def read(observed):
    """Kernels: the state-step kernel's bytes for the *decoding* slots (``h`` read and written, a slot's
    ``u``, ``delta``, ``B``, ``C``, ``y``; ``A`` and ``D`` once), a call a state-space layer a step, over
    819 GB/s, over the kernel's device seconds inside the traced ticks' decode programs. The kernel steps
    every slot: what it spends on slots that decode nothing lowers the share. ``None`` where the device
    ran no such kernel."""
    cfg, family = observed["config"], observed["family"]
    ticks = [t for t in _decode_programs.decode_ticks(observed) if t["ops"] is not None]
    seconds = sum(_decode_programs.seconds_of(t, KERNEL) for t in ticks)
    if not seconds:
        return None
    need = sum(t["dispatch"]["tick_block"] * family.mamba_layers(cfg) * family.state_step_bytes(cfg, t["dispatch"]["decoding"])
               for t in ticks)
    return 100.0 * need / _peaks(observed)["hbm_bytes_per_s"] / seconds
