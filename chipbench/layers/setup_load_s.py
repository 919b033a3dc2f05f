from chipbench.layers import _phase_log


def read(observed):
    """Compile caches: seconds of ``program.load`` (deserialize or compile through ``ProgramCache``) and of
    jax's own backend compiles (persistent-cache reads among them) before the window opened. The
    ``setup_programs`` note has them by program and by source, so that an eviction shows by name."""
    seconds = _phase_log.setup_seconds(observed)
    return seconds and seconds["load_s"]
