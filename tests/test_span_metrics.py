"""The benchmark's readers of the server's tick spans
(``bench/metrics/{tick_host_ms,logits_d2h_ms,compact_ms,slot_install_ms}.py``)
on hand-built runs: spans that start outside the window are left out, a
tick with no ``device_wait`` counts whole as host time, and a program
that records none of the spans (the parent of the change that added
them) reads nothing instead of raising.
"""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import RunData  # noqa: E402
from bench.spec import metric_module  # noqa: E402

EPOCH = 1000.0  # perf_counter seconds of the tracer's first event
T_OPEN, T_CLOSE = EPOCH + 1.0, EPOCH + 2.0


def _x(name, t, ms, parent=None):
    """An X event starting ``t`` s after the epoch, ``ms`` long."""
    ev = {"ph": "X", "name": name, "cat": "step", "pid": 1, "tid": 1,
          "ts": t * 1e6, "dur": ms * 1e3}
    if parent is not None:
        ev["args"] = {"parent": parent}
    return ev


def _tick(t, ms, *children):
    return [_x("tick", t, ms)] + [_x(n, t + dt, d, p)
                                  for n, dt, d, p in children]


SPANS = (
    # before the window: left out, children and all
    _tick(0.5, 10.0, ("device_wait", 0.001, 4.0, "decode"),
          ("logits_d2h", 0.005, 2.0, "decode"))
    + [_x("compact", 0.7, 50.0, "prefill_chunk"),
       _x("slot_install", 0.9, 100.0, "decode")]
    # 10 ms tick, 3 ms waiting and 1 ms copying: 6 ms of host time
    + _tick(1.1, 10.0, ("compact", 0.001, 2.0, "prefill_chunk"),
            ("slot_install", 0.004, 1.5, "decode"),
            ("device_wait", 0.006, 3.0, "decode"),
            ("logits_d2h", 0.009, 1.0, "decode"))
    # a prefill chunk that is not a prompt's last: no wait at all
    + _tick(1.2, 4.0, ("prefill_dispatch", 0.001, 2.0, "prefill_chunk"))
    + [_x("compact", 1.5, 4.0, "prefill_chunk"),
       _x("slot_install", 1.6, 2.5, "decode")]
    # starts in the window, its wait and copy after the close: counted
    # with the tick (14 ms of host time); the copy is not in the window
    + _tick(1.995, 20.0, ("device_wait", 0.005, 5.0, "decode"),
            ("logits_d2h", 0.011, 1.0, "decode"))
)

#: what a program without the new spans records
PARENT_SPANS = (_tick(1.1, 10.0, ("plan", 0.0, 0.1, "tick"),
                      ("decode", 0.001, 9.0, "tick"))
                + _tick(1.3, 12.0, ("prefill_chunk", 0.001, 11.0, "tick")))


def _run(events):
    return RunData(cell=None, seed=0, t_open=T_OPEN, t_close=T_CLOSE,
                   records=[], setup_s=0.0, compiles_in_window=[],
                   peak_bytes=0, device={}, tracer_events=list(events),
                   tracer_epoch=EPOCH)


@pytest.mark.parametrize("name,events,want", [
    ("tick_host_ms", SPANS, (6.0 + 4.0 + 14.0) / 3),
    ("logits_d2h_ms", SPANS, 1.0 / 3),
    ("compact_ms", SPANS, (2.0 + 4.0) / 2),
    ("slot_install_ms", SPANS, (1.5 + 2.5) / 2),
    ("tick_host_ms", PARENT_SPANS, None),
    ("logits_d2h_ms", PARENT_SPANS, None),
    ("compact_ms", PARENT_SPANS, None),
    ("slot_install_ms", PARENT_SPANS, None),
], ids=[f"{n}-{k}" for k in ("spans", "parent")
         for n in ("tick_host_ms", "logits_d2h_ms", "compact_ms",
                   "slot_install_ms")])
def test_span_metric_reader(name, events, want):
    got = metric_module(name).read(_run(events))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
