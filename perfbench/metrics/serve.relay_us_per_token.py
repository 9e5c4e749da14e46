"""Microseconds the HTTP proxy spent on one streamed item once it had
arrived: summed `fetch_us_sum` + `write_us_sum` (fetch the value; write the
chunk and drain) over summed `items` of the `relay::` spans of the window's
finished requests."""

from perfbench.lib.token_path import per_item


def read(run):
    return per_item(run, "relay", "items", "fetch_us_sum", "write_us_sum")
