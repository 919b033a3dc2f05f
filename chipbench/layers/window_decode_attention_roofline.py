from chipbench.layers import _mixed_ticks


def read(observed):
    """Kernels: the paged decode kernel of the WINDOW attention layers (a ring table a slot). The bytes of the rows
    inside the band for the kept steps (the program's ``window_rows_read``: ``min(t + 1, window)``), keys and
    values, with the queries and outputs at the window layers' heads, over the chip's memory bandwidth, over the
    seconds of the device operations whose name is ``paged_decode_attention_w<window>`` inside the traced ticks'
    decode programs. The kernel fetches whole pages: up to a page of rows below the band is in the seconds and not
    in the bytes. ``None`` where the program carries no count by kind or the trace names no such operation."""
    family = observed["family"]
    return _mixed_ticks.kind_roofline(observed, family.WINDOW, _mixed_ticks.window_kernel(observed), "window_rows_read")
