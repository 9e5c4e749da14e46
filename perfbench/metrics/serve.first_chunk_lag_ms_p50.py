"""Median time from a request's first token reaching the host (the end of
its `engine.prefill` span, the replica's clock) to its first chunk written
to the socket (`first_write_ts` of its `relay::` span, the proxy's clock;
one host, epoch-anchored): the consumer's wake, the replica's report, the
owner's callback and the proxy's write, once."""

from perfbench.lib.token_path import stream_percentile


def read(run):
    return stream_percentile(
        run, 50, lambda s: (s["relay"]["first_write_ts"] - s["prefill_end_us"])
        / 1e3 if s["relay"]["first_write_ts"] else None)
