"""Median of the engine's own `engine.prefill` span: from the admission
pass that gave the request its slot to its first token reaching the host."""

from perfbench.lib.program_spans import request_percentile_ms


def read(run):
    return request_percentile_ms(
        run, 50, lambda t: t["engine.prefill"]["dur"])
