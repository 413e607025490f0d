"""decode_step_ms (engine step): wall time of one decode step, from the
engine's own decode wall and step counters over the window."""

from bench import readings


def read(run):
    return readings.ms(readings.decode_step_s(run))
