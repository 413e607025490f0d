"""paged_decode_roofline (kernels): the paged decode attention kernel's
share of the chip's HBM bandwidth in the traced seconds.

Bytes: the valid KV that the decode forwards of the traced steps must
read, ``(context + rows) x kv_bytes_per_token`` summed over the steps that
began and ended inside the traced span (``Run.traced``; a step's forward
runs between the end of the step before it and its own end).  Time: the
device seconds of the kernel's ops in the trace (``op_seconds
["paged_decode"]``, every layer's call).  The bytes are token-granular,
below what the kernel really reads (whole pages), and the kernel's ops of
the steps cut by the span's edges add time and no bytes, so the share
cannot pass 100%.  Reads nothing without a trace or without the kernel
in it."""

KERNEL = "paged_decode"


def read(run):
    if run.trace is None or run.traced is None:
        return None
    seconds = run.trace.op_seconds.get(KERNEL)
    if not seconds:
        return None
    lo, hi = run.traced
    positions = 0
    for prev, s in zip(run.steps, run.steps[1:]):
        if lo <= prev.t and s.t <= hi and s.decoded:
            positions += s.context + s.decoded
    if not positions:
        return None
    nbytes = positions * run.sizes.kv_bytes_per_token
    return 100.0 * nbytes / seconds / run.peaks["hbm_bytes_per_s"]
