from chipbench import costs
from chipbench.layer_tools import kernel_seconds
from chipbench.peaks import peaks_for

KERNELS = {"fwd": "_fwd_kernel", "dq": "_dq_kernel", "dkv": "_dkv_kernel"}


def read(observed):
    """Kernels: the flash kernels' required operations (per chip: its share of batch and heads)
    over the bf16 peak, over their time in the trace."""
    cfg, traffic = observed["config"], observed["traffic"]
    if "seq" not in traffic:
        return None
    heads, _, d = observed["family"].attention_shape(cfg)
    need = seconds = 0.0
    for which, needle in KERNELS.items():
        s, calls = kernel_seconds(observed, needle)
        if s:
            seconds += s
            need += calls * costs.flash_attention_flops(traffic["batch"], traffic["seq"], heads, d, which) / observed["chips"]
    if not seconds:
        return None
    return 100.0 * need / peaks_for(observed["device"]["kind"])["bf16_flops"] / seconds
