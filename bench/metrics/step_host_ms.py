"""step_host_ms (engine step): host time of one engine step outside its
forwards: the wall time of the window's engine steps (the program's
``engine.step`` spans, summed in ``EngineStats.step_wall_s``) less their
decode and prefill forwards (``engine.decode_step``, ``engine.prefill``:
``decode_wall_s``, ``prefill_wall_s``), per step.  It holds the sampler,
the host pull of the tokens, retiring rows, admission, page claims and
the logits carry.  A program without the step counters reads nothing."""


def read(run):
    st = run.stats
    if not st.get("steps"):
        return None
    host = st["step_wall_s"] - st["decode_wall_s"] - st["prefill_wall_s"]
    return 1000.0 * host / st["steps"]
