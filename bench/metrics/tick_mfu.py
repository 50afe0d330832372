"""Whole step: model FLOPs the window's ticks needed over the ticks'
wall time at the chip's bf16 peak, in percent.

FLOPs come from shapes (``bench/flops.py``): each prefill token runs
the dense model, each decode token its tier's kept FF neurons, both
attend their live context, and each token pays the LM head.  Time is
the sum of the program's ``tick`` spans in the window."""
from bench import flops
from bench.peaks import peaks


def read(run):
    ticks = run.spans("tick")
    if not ticks or not run.plans:
        return None
    s = run.spec
    total = 0.0
    for _, pf, dec in run.plans:
        if pf is not None:
            start, n = pf
            total += sum(flops.token_flops(s, start + i + 1, s.d_ff)
                         for i in range(n))
        for pos, tier in dec:
            total += flops.token_flops(s, pos + 1, flops.tier_k(s, tier))
    busy = sum(d for _, d in ticks)
    peak = peaks(run.device["kind"])["bf16_flops"]
    return 100.0 * total / (busy * peak)
