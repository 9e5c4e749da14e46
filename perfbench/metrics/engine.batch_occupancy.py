"""Mean share of the cache slots that were busy at the decode steps
dispatched in the window."""


def read(run):
    t0, t1 = run["t_open"], run["t_open"] + run["seconds"]
    busy = [n for t, n, _ in run.get("replica", {}).get("steps", []) if t0 <= t < t1]
    if not busy:
        return None
    return 100.0 * sum(busy) / len(busy) / run["config"]["run"]["num_slots"]
