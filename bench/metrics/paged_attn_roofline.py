"""Kernel: the fused paged-attention Pallas kernel's share of its
roofline, in percent.

Device time is the sum of the kernel's ops in the traced window.  The
least time is, per call, the larger of its FLOPs over the chip's bf16
peak and its bytes over HBM bandwidth (the cell's yardstick,
``KERNEL_COUNTS["paged_attn"]``: for a Llama block the attended keys
and values read once, the new ones written once, queries in, float32
context out), summed over every call the window's ticks made: one per
layer for the prefill chunk and one per layer for the decode batch
(live rows only).  A yardstick with no ``paged_attn`` entry reads
nothing."""
from bench.peaks import peaks

#: how the kernel shows in the trace (see PERF.md, Layers)
KERNELS = {"paged_attn": ["paged_attn"]}


def least_time(run, counts):
    """(seconds, calls bound by bandwidth, calls) over the window."""
    s = run.spec
    pk = peaks(run.device["kind"])
    total, mem_bound, calls = 0.0, 0, 0
    for _, pf, dec in run.plans:
        rows = ([(pf[0], pf[1])] if pf is not None else [], [(p, 1) for p, _ in dec])
        for group in rows:
            if not group:
                continue
            f = b = 0.0
            for pos, S in group:
                fi, bi = counts(s, pos, S)
                f, b = f + fi, b + bi
            tf, tb = f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"]
            total += s.layers * max(tf, tb)
            mem_bound += tb >= tf
            calls += 1
    return total, mem_bound, calls


def read(run):
    counts = run.cell.arch.KERNEL_COUNTS.get("paged_attn")
    tr = run.trace
    if counts is None or tr is None or not tr.kernel_calls.get("paged_attn"):
        return None
    t_min, _, _ = least_time(run, counts)
    return 100.0 * t_min / tr.kernel_seconds["paged_attn"]
