"""Work a node-sharded HGCN step *requires* of the interconnect, from
the partition's shape.  As in ``costs.py`` the count is of what the
algorithm needs and nothing of how a schedule goes about it: a row
another shard owns is counted once however often the shard's edges name
it, padding and the rows an all-gather moves unasked are left out.  A
share of the interconnect's peak computed from it can only read low.
"""

from __future__ import annotations


def halo_bytes_step(rows_needed: float, widths, msg_bytes: int) -> float:
    """Bytes one device has to receive in a step: ``rows_needed`` rows of
    other shards (those its incoming edges name, each once), in the
    message type, for every layer's forward (the activations) and
    backward (the same rows of the cotangent)."""
    return 2.0 * rows_needed * sum(widths[1:]) * msg_bytes


def halo_seconds(rows_needed: float, widths, msg_bytes: int,
                 peaks: dict) -> float:
    """The least time the chip's interconnect could take for them, all
    of its links counted as if they led to the one peer."""
    return halo_bytes_step(rows_needed, widths, msg_bytes) / peaks[
        "ici_bytes_per_s"]
