"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device metrics.

* busy time: the union of the intervals in which an XLA op ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  clipped to the traced window and averaged over the devices;
* per-op device time, summed by op name (the ``breakdown`` list);
* per-kernel device time: ops whose name or string stats contain one of
  a kernel's patterns;
* idle gaps: the stretches of the window in which no op ran on device 0,
  each named by the host activity that covered it.

The window is the host annotation ``bench_window`` when the trace has
one, else the span of the device ops.  Run as a script on a trace
directory to print its planes, lines and busiest op names with their
stats (how a kernel's name is first found):

    python bench/trace_reduce.py <trace-dir>
"""
from __future__ import annotations

import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_ANNOTATION = "bench_window"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:") and \
        plane_name[len("/device:TPU:"):].isdigit()


@dataclass
class Trace:
    """What the reduction reads: device ops per device plane, and host
    events by name (start and duration in ns on the profiler clock)."""
    ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    host: Dict[str, List[Tuple[float, float]]] = field(default_factory=dict)
    op_stats: Dict[str, Dict[str, str]] = field(default_factory=dict)


def load(path: Path, host_names: Iterable[str] = (WINDOW_ANNOTATION,)
         ) -> Trace:
    from jax.profiler import ProfileData

    want = set(host_names)
    pd = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in pd.planes:
        if _is_device(plane.name):
            ops = tr.ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name = e.name
                    ops.append((name, e.start_ns, e.duration_ns))
                    if name not in tr.op_stats:
                        tr.op_stats[name] = {
                            k: v for k, v in e.stats if isinstance(v, str)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in want:
                        tr.host.setdefault(e.name, []).append(
                            (e.start_ns, e.duration_ns))
    return tr


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def window(tr: Trace) -> Tuple[float, float]:
    if tr.host.get(WINDOW_ANNOTATION):
        s, d = tr.host[WINDOW_ANNOTATION][0]
        return s, s + d
    starts = [s for ops in tr.ops.values() for _, s, _ in ops]
    ends = [s + d for ops in tr.ops.values() for _, s, d in ops]
    return min(starts), max(ends)


def _clip(ivs, w0, w1):
    return [(max(a, w0), min(b, w1)) for a, b in ivs if b > w0 and a < w1]


def matches(name: str, stats: Dict[str, str], patterns: Sequence[str]) -> bool:
    hay = [name] + list(stats.values())
    return any(p in h for p in patterns for h in hay)


@dataclass
class Reduced:
    window_s: float
    busy_s: float  # averaged over devices
    n_devices: int
    op_seconds: Dict[str, float]  # by op name, averaged over devices
    kernel_seconds: Dict[str, float]
    kernel_calls: Dict[str, int]
    gaps: List[Tuple[float, float]]  # idle stretches on device 0 (ns)


def reduce(tr: Trace, kernels: Optional[Dict[str, Sequence[str]]] = None,
           win: Optional[Tuple[float, float]] = None) -> Reduced:
    kernels = kernels or {}
    w0, w1 = win or window(tr)
    devices = sorted(tr.ops)
    if not devices:
        raise ValueError("the trace has no device ops")
    busy = 0.0
    op_s: Dict[str, float] = {}
    k_s = {k: 0.0 for k in kernels}
    k_n = {k: 0 for k in kernels}
    is_k = {}
    gaps: List[Tuple[float, float]] = []
    for i, dev in enumerate(devices):
        ivs = []
        for name, s, d in tr.ops[dev]:
            c = _clip([(s, s + d)], w0, w1)
            if not c:
                continue
            a, b = c[0]
            ivs.append((a, b))
            op_s[name] = op_s.get(name, 0.0) + (b - a) * 1e-9
            if name not in is_k:
                st = tr.op_stats.get(name, {})
                is_k[name] = [k for k, pats in kernels.items()
                              if matches(name, st, pats)]
            for k in is_k[name]:
                k_s[k] += (b - a) * 1e-9
                k_n[k] += 1
        u = union(ivs)
        busy += sum(b - a for a, b in u) * 1e-9
        if i == 0:
            edges = [w0] + [x for iv in u for x in iv] + [w1]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    n = len(devices)
    return Reduced(
        window_s=(w1 - w0) * 1e-9, busy_s=busy / n, n_devices=n,
        op_seconds={k: v / n for k, v in op_s.items()},
        kernel_seconds={k: v / n for k, v in k_s.items()},
        kernel_calls={k: v // n for k, v in k_n.items()}, gaps=gaps)


def name_gaps(gaps: List[Tuple[float, float]],
              spans: List[Tuple[str, float, float]], top: int = 10
              ) -> List[List]:
    """The ``top`` longest gaps as ``[activity, seconds]``; a gap is
    named by the innermost (shortest) host span that holds its middle,
    ``spans`` being ``(name, start_ns, end_ns)`` on the trace clock."""
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (a + b)
        holding = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        out.append([min(holding)[1] if holding else "no host span",
                    (b - a) * 1e-9])
    return out


def _describe(trace_dir: str) -> None:
    from jax.profiler import ProfileData

    path = find_xplane(Path(trace_dir))
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name}: {[ln.name for ln in lines][:12]}")
    tr = load(path)
    red = reduce(tr)
    print(f"window {red.window_s:.6f} s, busy {red.busy_s:.6f} s on "
          f"{red.n_devices} device(s)")
    for name, sec in sorted(red.op_seconds.items(), key=lambda x: -x[1])[:25]:
        print(f"  {sec:.6f} s  {name}  {tr.op_stats.get(name, {})}")


if __name__ == "__main__":
    _describe(sys.argv[1])
