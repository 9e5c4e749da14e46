"""95th percentile of the engine's own `engine.queue` span: from `submit()`
to the admission pass that gave the request a slot. Every request waits
here for the running step to end, slot or no slot (a bounded wait, so its
p95 is the sharp edge); a request that found no slot waits on top."""

from perfbench.lib.program_spans import request_percentile_ms


def read(run):
    return request_percentile_ms(
        run, 95, lambda t: t["engine.queue"]["dur"])
