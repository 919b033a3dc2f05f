from chipbench.layers import _mixed_ticks


def read(observed):
    """Scheduler: of the bytes of the two pools that the slots hold when a tick ends (``full_pages`` and
    ``window_pages`` of ``engine.tick.done``, each times its kind's page over its layers: the family's
    ``page_bytes``), the share in the window layers' pool: what the band costs beside the contexts. Summed over the
    traced decode ticks. ``None`` where the program carries no such count."""
    ticks = _mixed_ticks.ticks(observed)
    cfg, family = observed["config"], observed["family"]
    block = cfg["bench"]["serving"]["paged_block_size"]
    window = sum(t["stats"].get("window_pages", 0) for t in ticks) * family.page_bytes(cfg, family.WINDOW, block)
    held = window + sum(t["stats"].get("full_pages", 0) for t in ticks) * family.page_bytes(cfg, family.FULL, block)
    return 100.0 * window / held if held else None
