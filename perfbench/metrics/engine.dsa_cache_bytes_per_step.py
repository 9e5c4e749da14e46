"""Bytes of its cache one decode step of the Keye model needs, mean over the
window's steps: (`index_rows` x the stored indexer key's 256 B +
`selected_rows` x a position's 2,048 B of `[k ; v]`) x 6 layers, the
program's own counters on `engine.step`, priced by `lib.keye_counts`. A step
that read every row would move `kv_rows` x 2,048 B x 6."""

from perfbench.lib import keye_counts


def read(run):
    got = [keye_counts.cache_bytes_per_step(run["config"], a["index_rows"],
                                            a["selected_rows"])
           for a in keye_counts.step_args(run)]
    return sum(got) / len(got) if got else None
