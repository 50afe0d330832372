"""Scheduler: 90th percentile over requests due in the window of due ->
prefill start (``RequestTimeline.prefill_start_t``); a request not yet
started when the window closes counts with the time it had waited."""
import numpy as np


def read(run):
    waits = []
    for r in run.records:
        if not run.in_window(r.due) or r.aborted:
            continue
        start = r.prefill_start_t
        if start is None or start >= run.t_close:
            start = run.t_close
        waits.append((start - r.due) * 1e3)
    return float(np.percentile(waits, 90)) if waits else None
