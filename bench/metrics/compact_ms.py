"""Server / model step: mean host time of GRIFFIN selection and
compaction at the end of a prompt (the program's ``compact`` span
around ``griffin.select_and_compact``), over prompts whose compaction
starts in the window.  Its device ops run later, inside the next
``device_wait``."""
import numpy as np


def read(run):
    spans = run.spans("compact")
    return float(np.mean([d for _, d in spans]) * 1e3) if spans else None
