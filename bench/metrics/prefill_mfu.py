"""prefill_mfu (model step): FLOPs the window's prefills require (real
prompt tokens, causal attention counted half) over the prefill wall time,
as a share of the chip's bf16 peak."""

from bench import readings


def read(run):
    return readings.prefill_mfu(run)
