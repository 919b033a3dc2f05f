"""What the readers of an aligned window's counts share: the traced decode ticks whose ``engine.tick.done``
carries them (``attn_rows_read``, ``context_rows``, ``chunks_pooled``, ``windows_closed``, ``exact_pages``,
``summary_pages``: the program's, PR 42), from ``_decode_programs``. A program without the counts (or a cell
whose model has no such window: the counts are there and 0) gives nothing to read."""

from __future__ import annotations

from chipbench import trace
from chipbench.layers import _decode_programs

KERNEL = "paged_decode_attention"


def ticks(observed: dict) -> list:
    """The traced decode ticks in which some kept step attended under an aligned window."""
    return [t for t in _decode_programs.decode_ticks(observed) if t["stats"].get("attn_rows_read")]


def kernel_seconds(tick: dict) -> float:
    """Device seconds of the operations that ARE the paged decode kernel (``trace.op_family`` of the event's
    name, not a needle in its HLO line: a fusion that names the kernel among its operands is not it)."""
    return sum(d for n, d in tick["ops"] or () if trace.op_family(n) == KERNEL)


def tick_bytes(observed: dict, tick: dict) -> float:
    """Keys, values, queries and outputs the kernel moves in one tick over every layer: the family's count of the
    rows the tick's kept steps read (a summary a chunk of the closed windows, the open window's rows)."""
    steps = tick["dispatch"]["decoding"] * tick["dispatch"]["tick_block"]
    return observed["family"].cache_bytes_per_decode_step(observed["config"], tick["stats"]["attn_rows_read"], steps)
