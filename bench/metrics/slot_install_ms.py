"""Server / model step: mean host time of writing compacted FF trees
into the decode slot buffers (the program's ``slot_install`` span,
opened only when at least one slot is written), over installs that
start in the window.  Its device ops run later, inside the next
``device_wait``."""
import numpy as np


def read(run):
    spans = run.spans("slot_install")
    return float(np.mean([d for _, d in spans]) * 1e3) if spans else None
