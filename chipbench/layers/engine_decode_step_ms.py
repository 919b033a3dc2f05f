from chipbench import program_trace


def read(observed):
    """Decode tick: ``engine.decode.dispatch`` to the end of ``engine.decode.sync`` over ``tick_block``,
    median over every traced tick, those that prefilled too."""
    phases = program_trace.phases_of(observed)
    return phases and program_trace.decode_step_ms(phases)
