"""Server / model step: host time per tick spent copying step outputs
(the float32 logits) from the device, after the device finished: the
program's ``logits_d2h`` spans that start in the window, summed, over
the ``tick`` spans that start in the window."""


def read(run):
    copies, ticks = run.spans("logits_d2h"), run.spans("tick")
    if not copies or not ticks:
        return None
    return sum(d for _, d in copies) / len(ticks) * 1e3
