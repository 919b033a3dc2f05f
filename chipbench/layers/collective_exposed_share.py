def read(observed):
    """Mesh: time the core spent inside collective operations (the ops line is serial, so no
    compute ran beside them), over the traced window, mean over the chips."""
    t = observed.get("trace")
    if not t or t["devices"] < 2:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]
