from chipbench import stats


def read(observed):
    """Train step: the median fenced step."""
    return stats.median(observed["step_ms"]) if observed.get("step_ms") else None
