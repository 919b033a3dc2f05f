"""What the readers of layers of two kinds (full and window attention, a pool and a table a kind) share: the traced
decode ticks whose ``engine.tick.done`` carries the counts by kind (``window_rows_read`` beside ``context_rows``,
``full_pages``, ``window_pages``: the program's, PR 48), from ``_decode_programs``, and the two shapes of the paged
kernel by their device names. A program without the counts (or a cell whose model has one kind of layer: the
counts are there and 0) gives nothing to read."""

from __future__ import annotations

from chipbench import trace
from chipbench.layers import _decode_programs
from chipbench.peaks import peaks_for

FULL_KERNEL = "paged_decode_attention"


def window_kernel(observed: dict) -> str:
    """The device name of the window layers' kernel: the call's name carries its band."""
    return f"{FULL_KERNEL}_w{observed['config']['sliding_window']}"


def ticks(observed: dict) -> list:
    """The traced decode ticks in which some kept step attended under a band beside full layers."""
    return [t for t in _decode_programs.decode_ticks(observed) if t["stats"].get("window_rows_read")]


def steps(tick: dict) -> int:
    return tick["dispatch"]["decoding"] * tick["dispatch"]["tick_block"]


def kernel_seconds(tick: dict, kernel: str) -> float:
    """Device seconds of the operations whose NAME is ``kernel`` (``trace.op_family`` of the event's name, not a
    needle in its HLO line: the window kernel's name begins with the full kernel's, and a fusion that names a
    kernel among its operands is not it)."""
    return sum(d for n, d in tick["ops"] or () if trace.op_family(n) == kernel)


def kind_roofline(observed: dict, kind: str, kernel: str, rows: str):
    """The bytes the layers of ``kind`` move through the paged kernel in the traced ticks (the family's
    ``attention_bytes`` of the tick's count ``rows``, with each slot-step's queries and outputs at that kind's
    heads) over the chip's memory bandwidth, over the seconds of the device operations named ``kernel``."""
    found = [t for t in ticks(observed) if t["ops"]]
    seconds = sum(kernel_seconds(t, kernel) for t in found)
    if not seconds:
        return None
    cfg, family = observed["config"], observed["family"]
    need = sum(family.attention_bytes(cfg, kind, t["stats"][rows], steps(t)) for t in found)
    return 100.0 * need / peaks_for(observed["device"]["kind"])["hbm_bytes_per_s"] / seconds
