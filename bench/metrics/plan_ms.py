"""Scheduler: mean host time of the program's ``plan`` span per tick
(``Scheduler.plan_step``), over ticks that start in the window."""
import numpy as np


def read(run):
    spans = run.spans("plan")
    return float(np.mean([d for _, d in spans]) * 1e3) if spans else None
