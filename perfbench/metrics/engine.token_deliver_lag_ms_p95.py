"""95th percentile over the window's finished requests of how long a
reaped token waited for its consumer: `deliver_lag_us_sum` / `tokens` of the
request's `engine.stream` span (a batch of tokens in the consumer's hand,
less the stamp of the reap that appended it)."""

from perfbench.lib.token_path import stream_percentile


def read(run):
    return stream_percentile(
        run, 95, lambda s: s["engine.stream"]["deliver_lag_us_sum"] / 1e3
        / s["engine.stream"]["tokens"] if s["engine.stream"]["tokens"] else None)
