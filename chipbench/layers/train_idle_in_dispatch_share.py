from chipbench import program_trace


def read(observed):
    """Device: idle time inside ``train.step`` spans (the device waiting for the next dispatch), over the window."""
    phases = program_trace.phases_of(observed)
    return phases and program_trace.idle_share_within(phases, "train.step")
