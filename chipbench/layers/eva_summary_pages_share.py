from chipbench.layers import _eva_ticks


def read(observed):
    """Scheduler: of the pool's pages the slots hold when a tick ends (``exact_pages`` + ``summary_pages`` of
    ``engine.tick.done``), the share that holds summaries: what the linearised past costs the pool. Summed over
    the traced decode ticks. ``None`` where the program carries no such count."""
    ticks = _eva_ticks.ticks(observed)
    summary = sum(t["stats"].get("summary_pages", 0) for t in ticks)
    held = summary + sum(t["stats"].get("exact_pages", 0) for t in ticks)
    return 100.0 * summary / held if held else None
