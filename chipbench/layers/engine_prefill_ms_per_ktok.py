from chipbench import program_trace


def read(observed):
    """Prefill programs: a request's first ``engine.prefill.dispatch`` to the end of its
    ``engine.prefill.sync``, per 1000 prompt tokens, median."""
    phases = program_trace.phases_of(observed)
    return phases and program_trace.prefill_ms_per_ktok(phases)
