"""device_idle_share (device): share of the traced seconds in which no
operation ran on the chip (bench/trace.py)."""

from bench import readings


def read(run):
    return readings.idle_share(run)
