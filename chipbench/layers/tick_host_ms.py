from chipbench import program_trace


def read(observed):
    """Engine host loop: ``engine.tick`` less the phases in which the host waits for the device, median."""
    phases = program_trace.phases_of(observed)
    return phases and program_trace.tick_host_ms(phases)
