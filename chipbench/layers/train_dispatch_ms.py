from chipbench import program_trace


def read(observed):
    """Train step: the host's cost of one ``step(batch)`` call (``train.step``), median."""
    phases = program_trace.phases_of(observed)
    return phases and program_trace.span_ms(phases, "train.step")
