"""Microseconds the replica spent handing one streamed item to its owner:
summed `report_us_sum` (serialise + `report_dynamic_return`) over summed
`items` of the `stream::handle_request` spans of the window's finished
requests."""

from perfbench.lib.token_path import per_item


def read(run):
    return per_item(run, "stream", "items", "report_us_sum")
