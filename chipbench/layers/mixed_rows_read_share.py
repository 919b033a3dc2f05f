from chipbench.layers import _mixed_ticks


def read(observed):
    """ops/paged_kv cache: of the rows one table for every layer would have a decode step read (``context_rows``,
    the sum of ``t + 1`` over the kept steps, on every attention layer), the share that a table a kind reads: the
    full layers the contexts, the window layers the band (``window_rows_read``: ``min(t + 1, window)``); both
    counts of ``engine.tick.done``, summed over the traced decode ticks and weighted by the layers of each kind.
    100 says that no sequence has left its first window. A reading of the traffic, nobody's aim. ``None`` where the
    program carries no such count."""
    ticks = _mixed_ticks.ticks(observed)
    context = sum(t["stats"].get("context_rows", 0) for t in ticks)
    if not context:
        return None
    cfg, family = observed["config"], observed["family"]
    full, window = family.layers_of(cfg, family.FULL), family.layers_of(cfg, family.WINDOW)
    band = sum(t["stats"]["window_rows_read"] for t in ticks)
    return 100.0 * (full * context + window * band) / ((full + window) * context)
