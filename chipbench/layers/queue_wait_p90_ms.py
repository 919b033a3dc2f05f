from chipbench import stats


def read(observed):
    """Scheduler: submit to admission, as the program's own ``ServingMetrics`` counted it."""
    waits = observed.get("counters", {}).get("queue_wait_ms")
    return stats.percentile(waits, 90.0) if waits else None
