"""Median duration of the router's `route::` span: picking the replica and
handing the request to the actor-task submit, per request of the window."""

from perfbench.lib.program_spans import request_percentile_ms


def read(run):
    return request_percentile_ms(
        run, 50, lambda t: t["route"]["dur"])
