"""prefill_real_share (engine step): share of the prefill forwards' token
positions that hold a real prompt token, over the window: the engine's
``prefill_prompt_tokens`` over its padded ``prefill_tokens``.  The rest is
padding to the prefill bucket.  A program without the real-token counter
reads nothing."""


def read(run):
    real = run.stats.get("prefill_prompt_tokens")
    padded = run.stats.get("prefill_tokens")
    if real is None or not padded:
        return None
    return 100.0 * real / padded
