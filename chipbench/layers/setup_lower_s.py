from chipbench.layers import _phase_log


def read(observed):
    """Compile caches: seconds of ``program.lower`` (trace and lower through ``ProgramCache``) and of
    jax's own trace and lower events before the window opened: what no cache shortens and every process
    pays again. From the program's phase log; ``None`` where the program keeps none."""
    seconds = _phase_log.setup_seconds(observed)
    return seconds and seconds["lower_s"]
