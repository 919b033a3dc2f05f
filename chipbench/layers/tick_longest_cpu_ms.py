from chipbench.layers import _phase_log


def read(observed):
    """Engine host loop: of the window's longest tick (``tick_longest_ms``), the thread's CPU ms: near the
    wall it computed, near zero it waited (blocked or descheduled)."""
    tick = _phase_log.longest_tick(observed)
    return tick and tick.cpu_ns / 1e6
