"""Server / model step: mean host time of a tick, over ticks that start
in the window: the program's ``tick`` span less the ``device_wait`` and
``logits_d2h`` spans inside it (the waits for the device and the logits
copies to the host).  What is left is planning, input building,
dispatch, GRIFFIN compaction and slot installs, and the argmax.  None
where the program records no ``device_wait`` span."""
from bisect import bisect_left

import numpy as np

#: spans in which the host waits for the device or copies from it
WAITS = ("device_wait", "logits_d2h")


def read(run):
    ev = [e for e in run.tracer_events if e.get("ph") == "X"]
    if not any(e["name"] == "device_wait" for e in ev):
        return None
    ticks = run.spans("tick")
    if not ticks:
        return None
    waits = sorted((run.tracer_epoch + e["ts"] * 1e-6, e["dur"] * 1e-6)
                   for e in ev if e["name"] in WAITS)
    starts = [s for s, _ in waits]
    cum = np.concatenate([[0.0], np.cumsum([d for _, d in waits])])
    host = []
    for t0, d in ticks:
        a, b = bisect_left(starts, t0), bisect_left(starts, t0 + d)
        host.append(d - (cum[b] - cum[a]))
    return float(np.mean(host) * 1e3)
