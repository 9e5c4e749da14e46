"""Median time from the end of the router's `route::` span to the start of
the replica's `task::handle_request` span: the push to the replica's
process, its mailbox and the dispatch onto an execution thread."""

from perfbench.lib.program_spans import request_percentile_ms


def read(run):
    return request_percentile_ms(
        run, 50, lambda t: t["task"]["ts"] - (t["route"]["ts"] + t["route"]["dur"]))
