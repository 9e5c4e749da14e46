"""Median time a request spent in the HTTP proxy before routing began:
from the program's `ingress::` span opening (request read off the socket)
to its `route::` span opening, per request trace of the window."""

from perfbench.lib.program_spans import request_percentile_ms


def read(run):
    return request_percentile_ms(
        run, 50, lambda t: t["route"]["ts"] - t["ingress"]["ts"])
