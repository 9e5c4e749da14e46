"""Time in which a collective runs on a device and no compute does, as a
share of the traced window (mean over chips)."""


def read(run):
    tr = run.get("trace")
    if not tr or run["cell"]["chips"] < 2:
        return None
    return 100.0 * tr["exposed_collective_s"] / tr["window_s"]
