"""Process start to window open: loading, chip open, weights, compilation or
cache reads, warm-up, and for serving the pre-window traffic. Host clock."""


def read(run):
    return run["t_open"] - run["t_start"]
