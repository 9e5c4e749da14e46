"""From asking ray_tpu for the worker or replica to that process seeing
its first device: lease, cold spawn, imports, chip open."""


def read(run):
    return run["t_device"] - run["t_ask"]
