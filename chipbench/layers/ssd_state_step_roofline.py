from chipbench import trace
from chipbench.layer_tools import _peaks
from chipbench.layers import _decode_programs

KERNEL = "ssd_state_step"  # the Mamba-2 state-step kernel's device name (ops/pallas_ssd_step.py, ``name=``)


def read(observed):
    """Kernels: the Mamba-2 state-step kernel's bytes for the *decoding* slots (``h`` read and written, a
    slot's ``x``, ``delta``, ``B``, ``C``, ``y``; ``A`` and ``D`` once: the family's ``ssd_state_step_bytes``),
    a call a state-space layer a step, over 819 GB/s, over the kernel's own device seconds inside the traced
    ticks' decode programs. The kernel is the device operation whose *name* is ``ssd_state_step``
    (``trace.op_family`` of the event: ``%ssd_state_step.3 = ...`` and not an operation that only names it
    among its operands, as the fusion that consumes its ``y`` does). ``None`` where the device ran no such
    kernel or the family states no such bytes."""
    cfg, family = observed["config"], observed["family"]
    if not hasattr(family, "ssd_state_step_bytes"):
        return None
    ticks = [t for t in _decode_programs.decode_ticks(observed) if t["ops"] is not None]
    seconds = sum(d for t in ticks for n, d in t["ops"] if trace.op_family(n) == KERNEL)
    if not seconds:
        return None
    need = sum(t["dispatch"]["tick_block"] * family.mamba_layers(cfg) * family.ssd_state_step_bytes(cfg, t["dispatch"]["decoding"])
               for t in ticks)
    return 100.0 * need / _peaks(observed)["hbm_bytes_per_s"] / seconds
