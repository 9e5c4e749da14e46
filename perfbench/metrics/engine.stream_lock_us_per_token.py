"""Microseconds a request's consumer thread spent on the engine's
bookkeeping lock for one token, from asking for it to releasing it (the wait
to enter and the copy of the answer so far): summed `lock_us_sum` over summed
`tokens` of the `engine.stream` spans of the window's finished requests."""

from perfbench.lib.token_path import per_item


def read(run):
    return per_item(run, "engine.stream", "tokens", "lock_us_sum")
