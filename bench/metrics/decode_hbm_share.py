"""decode_hbm_share (model step): bytes the decode steps require (weights
once, valid KV read once, new KV written once) over their wall time, as a
share of the chip's HBM bandwidth."""

from bench import readings


def read(run):
    return readings.decode_share(run, "bytes")
