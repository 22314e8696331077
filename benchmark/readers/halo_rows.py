"""Rows the exchange moves in a step, all shards together: the rows the
chosen schedule delivers to a shard in one pass of one layer (the
program's gauge ``node_shard/halo_rows_moved_sum``, set at partition
time) x two passes x the layers.  A program without the gauge gives
nothing to read."""


def read(red, facts, peaks, spec):
    moved = facts.get(spec["gauge"])
    if moved is None or "widths" not in facts:
        return None
    return 2.0 * moved * (len(facts["widths"]) - 1)
