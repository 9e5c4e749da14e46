"""Output tokens the client received inside the window / its seconds
(tokens of requests sent before it opened count while they arrive in it)."""


def read(run):
    if "rows" not in run:
        return None
    n = sum(1 for r in run["rows"] for t in r["arrivals_s"]
            if 0.0 <= t < run["seconds"])
    return n / run["seconds"]
