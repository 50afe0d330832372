"""Device: share of the traced window in which no XLA op ran on the
chip, 100 * (1 - busy / window), from the profiler trace."""


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
