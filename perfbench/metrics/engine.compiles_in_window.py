"""Programs jax lowered in the replica while the window was open (none,
if every shape the traffic reaches was warmed)."""


def read(run):
    times = run.get("replica", {}).get("lowering_times")
    if times is None:
        return None
    t0, t1 = run["t_open"], run["t_open"] + run["seconds"]
    return float(sum(1 for t in times if t0 <= t < t1))
