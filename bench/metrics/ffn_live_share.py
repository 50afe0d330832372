"""Server / model step: how much of the FF weights a GRIFFIN decode
reads — the mean over layers of |U| / d_ff, U the union of the decode
batch's selected neurons that the decode FF reads (the
``ffn_live_share`` arg of the program's ``decode_dispatch`` spans),
over dispatches that start in the window, in %.  None where no dispatch
carries it (dense ticks, or a program without the union buffer)."""
import numpy as np


def read(run):
    vals = [e["args"]["ffn_live_share"] for e in run.tracer_events
            if e.get("ph") == "X" and e["name"] == "decode_dispatch"
            and "ffn_live_share" in e.get("args", {})
            and run.in_window(run.tracer_epoch + e["ts"] * 1e-6)]
    return float(np.mean(vals) * 100) if vals else None
