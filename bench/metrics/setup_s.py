"""setup_s: seconds from the start of the process to the start of the
window: weights drawn, every shape warmed (compiled or loaded from the
persistent cache) and the warm segment of the traffic served."""


def read(run):
    return run.setup_s
