from chipbench.layers import _phase_log


def read(observed):
    """Engine host loop: of the window's longest tick (``tick_longest_ms``), the ms under its ``*.sync``
    children: the device or the runtime held the thread; the remainder is the host's."""
    tick = _phase_log.longest_tick(observed)
    return tick and tick.child_ms(".sync")
