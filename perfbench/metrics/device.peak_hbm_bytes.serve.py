"""Peak bytes in use on the chip, as the backend reports it."""


def read(run):
    return run["memory_peak_bytes"] if "rows" in run else None
