"""batch_occupancy (admission layer): rows fed to each decode forward,
on average over the window (engine counters)."""

from bench import readings


def read(run):
    return readings.occupancy(run)
