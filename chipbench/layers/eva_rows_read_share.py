from chipbench.layers import _eva_ticks


def read(observed):
    """ops/paged_kv cache: of the rows of context the decoding slots stand at (``context_rows``: the sum of ``t +
    1`` over the kept steps), the share a decode step attends to (``attn_rows_read``: a summary for every chunk of
    the closed windows and the open window's rows); both counts of ``engine.tick.done``, summed over the traced
    decode ticks. 100 says that no sequence has closed a window (plain attention through the same kernel); a
    sequence past a close reads a sixteenth of what it left behind. A reading of the traffic, nobody's aim.
    ``None`` where the program carries no such count."""
    ticks = _eva_ticks.ticks(observed)
    context = sum(t["stats"].get("context_rows", 0) for t in ticks)
    return 100.0 * sum(t["stats"]["attn_rows_read"] for t in ticks) / context if context else None
