from chipbench import stats


def read(observed):
    """Engine host loop: how long a due request waited for ``step()`` to return before it was submitted."""
    return stats.percentile(observed["late_ms"], 90.0) if observed.get("late_ms") else None
