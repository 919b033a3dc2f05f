from chipbench.layer_tools import idle_within_span


def read(observed):
    """Engine host loop: device idle time inside the benchmark's ``step`` spans, over those spans."""
    return idle_within_span(observed, "step")
