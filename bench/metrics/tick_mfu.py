"""Whole step: model FLOPs the window's ticks needed over the ticks'
wall time at the chip's bf16 peak, in percent.

FLOPs come from shapes (the cell's yardstick, ``token_flops``): each
prefill token runs the dense model, each decode token what its tier
keeps, both attend their live context, and each token pays the LM head.
Time is the sum of the program's ``tick`` spans in the window."""
from bench.peaks import peaks


def read(run):
    ticks = run.spans("tick")
    if not ticks or not run.plans:
        return None
    arch, s = run.cell.arch, run.spec
    dense = arch.keep(s, 1.0)
    total = 0.0
    for _, pf, dec in run.plans:
        if pf is not None:
            start, n = pf
            total += sum(arch.token_flops(s, start + i + 1, dense)
                         for i in range(n))
        for pos, tier in dec:
            total += arch.token_flops(s, pos + 1, arch.keep(s, tier))
    busy = sum(d for _, d in ticks)
    peak = peaks(run.device["kind"])["bf16_flops"]
    return 100.0 * total / (busy * peak)
