"""Milliseconds a streamed item lay in the owner's process: from
`rpc_report_dynamic_return` stamping its ref on arrival to its chunk written
and drained by the HTTP proxy's loop. Summed `arrive_lag_us_sum` over summed
`items` of the `relay::` spans of the window's finished requests. The fetch
and the write are `serve.relay_us_per_token`: the rest is the loop being
woken late."""

from perfbench.lib.token_path import per_item


def read(run):
    us = per_item(run, "relay", "items", "arrive_lag_us_sum")
    return None if us is None else us / 1e3
