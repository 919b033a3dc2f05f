from chipbench import program_trace

PREFILL_PROGRAMS = ("prefill_b", "chunk_", "paste_row")


def read(observed):
    """Prefill programs: device seconds of the fused buckets, chunk windows and the paste over device
    busy seconds. ``None`` where no module bears a prefill program's name (a CPU, or a program whose
    buckets are all jitted as ``prefill``)."""
    phases, t = program_trace.phases_of(observed), observed.get("trace")
    if not phases or not t or not any(k.startswith(PREFILL_PROGRAMS[:2]) for k in phases["program_seconds"]):
        return None
    return program_trace.program_share(phases, t["busy_s"], PREFILL_PROGRAMS)
