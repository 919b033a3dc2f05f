def read(observed):
    """Compile caches: programs requested from the backend during set-up (cache hits among them)."""
    return observed.get("warm_programs")
