"""output_tok_s: output tokens emitted in the window, finished requests
or not, over the window; tokens a preemption discarded do not count."""

from bench import readings


def read(run):
    return readings.output_tokens_per_s(run) or None
