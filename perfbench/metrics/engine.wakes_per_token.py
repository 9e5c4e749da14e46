"""Thread wakes a token took: summed `wakes` over summed `tokens` of the
`engine.stream` spans of the window's finished requests (a wake is a return
from the engine's condition wait; every reap and every submit notifies every
waiter). About 1.0 is the floor: only a token found already waiting on the
pass that follows a `yield` takes no wake."""

from perfbench.lib.token_path import per_item


def read(run):
    return per_item(run, "engine.stream", "tokens", "wakes")
