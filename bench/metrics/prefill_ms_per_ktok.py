"""prefill_ms_per_ktok (engine step): prefill wall time in the window per
1000 real prompt tokens prefilled."""

from bench import readings


def read(run):
    return readings.prefill_ms_per_ktok(run)
