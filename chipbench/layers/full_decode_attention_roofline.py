from chipbench.layers import _mixed_ticks


def read(observed):
    """Kernels: the paged decode kernel of the FULL attention layers of a model that also has window layers. The
    bytes of the rows the kept steps stood at (the program's ``context_rows``), keys and values, with the queries
    and outputs at the full layers' heads, over the chip's memory bandwidth, over the seconds of the device
    operations whose name is ``paged_decode_attention`` inside the traced ticks' decode programs. Steps a slot
    computed past its last kept token are in the seconds and not in the bytes: the share reads low by them.
    ``None`` where the program carries no count by kind or the trace names no such operation (a CPU's)."""
    family = observed["family"]
    return _mixed_ticks.kind_roofline(observed, family.FULL, _mixed_ticks.FULL_KERNEL, "context_rows")
