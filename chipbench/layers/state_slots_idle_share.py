from chipbench.layers import _decode_programs


def read(observed):
    """Scheduler: of the slots whose recurrent state the decode ticks stepped, the share in which no
    request decodes: the ``state_slots_idle`` count of ``engine.tick.done`` (slots x steps) over the
    engine's slots x the tick's steps, mean over the traced decode ticks. The state's traffic grows with
    the slots, live or not: this is the share of it that serves nobody. ``None`` where the program
    carries no such count."""
    slots = observed["config"]["bench"]["serving"]["num_slots"]
    ticks = [t for t in _decode_programs.decode_ticks(observed) if "state_slots_idle" in t["stats"]]
    if not ticks:
        return None
    per = [t["stats"]["state_slots_idle"] / (slots * t["dispatch"]["tick_block"]) for t in ticks]
    return 100.0 * sum(per) / len(per)
