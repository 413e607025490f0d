"""decode_mfu (model step): FLOPs the decode steps require (resident rows,
valid KV) over their wall time, as a share of the chip's bf16 peak."""

from bench import readings


def read(run):
    return readings.decode_share(run, "flops")
