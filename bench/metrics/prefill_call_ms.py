"""Server / model step: mean of the program's ``prefill_chunk`` span (one
chunk through the model; on a prompt's last chunk also GRIFFIN selection
and compaction) over chunks in the window."""
import numpy as np


def read(run):
    spans = run.spans("prefill_chunk")
    return float(np.mean([d for _, d in spans]) * 1e3) if spans else None
