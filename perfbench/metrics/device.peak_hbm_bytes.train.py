"""Peak bytes in use on the fullest chip, as the backend reports it."""


def read(run):
    return run["memory_peak_bytes"] if "step_end_s" in run else None
