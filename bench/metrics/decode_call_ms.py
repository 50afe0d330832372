"""Server / model step: mean of the program's ``decode`` span (inputs,
dispatch, device work, logits on the host, argmax) over ticks in the
window."""
import numpy as np


def read(run):
    spans = run.spans("decode")
    return float(np.mean([d for _, d in spans]) * 1e3) if spans else None
