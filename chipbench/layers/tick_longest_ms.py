from chipbench.layers import _phase_log


def read(observed):
    """Engine host loop: wall of the longest ``engine.tick`` begun inside the whole measured window, from
    the program's phase log (every tick of the run, not the traced four seconds). A few medians in a
    sound run, thousands of ms in a stalled one: it says whether the run's end-to-end numbers are to be
    believed. ``None`` where the program keeps no such log."""
    tick = _phase_log.longest_tick(observed)
    return tick and tick.wall_ns / 1e6
