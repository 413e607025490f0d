"""itl_p50_ms: median gap between consecutive tokens of one request, over
every token emitted in the window (step-granular: a token's time is the
end of the engine step that emitted it).  The median and not a tail: on
this node about one step in nine carries a prefill, so the 95th
percentile sits on the edge between plain and prefill-carrying steps and
flips between them from run to run."""

from bench import readings, stats


def read(run):
    return readings.ms(stats.percentile(readings.itl_values(run), 50))
