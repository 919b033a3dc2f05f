from chipbench import program_trace


def read(observed):
    """Engine host loop: how long a first token that is on the host waits for ``step()`` to return
    (end of ``engine.tick`` less end of ``engine.prefill.sync``), median over the traced window."""
    phases = program_trace.phases_of(observed)
    return phases and program_trace.first_token_hold_ms(phases)
