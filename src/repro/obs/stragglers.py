"""Per-tick step-time telemetry.

A single tick whose duration exceeds ``threshold`` x the rolling median
of recent ticks increments the ``serving_straggler_ticks`` counter and
logs a warning.  This catches one-off stalls — a recompile, an
allocator scramble, a COW burst — that an EWMA would smooth away.
Every tick also lands in the ``serving_tick_seconds`` histogram.

The current tick is compared against the median *before* being added
to the window, so a spike cannot dilute its own baseline.  The counter
counts every flagged tick; the *log line* is throttled to one per
``log_every`` flags — mixed workloads flag systematically (a prefill
chunk is legitimately several decode ticks long), and per-tick warnings
would drown the serving log.
"""
from __future__ import annotations

import logging
from collections import deque
from statistics import median

from repro.obs.registry import Registry, exp_buckets

__all__ = ["StepTimeMonitor"]

logger = logging.getLogger(__name__)

# 10 µs .. ~84 s, x2 per bucket: covers tiny-CPU ticks through real
# accelerator prefill chunks in 24 buckets.
TICK_BUCKETS = exp_buckets(1e-5, 2.0, 24)


class StepTimeMonitor:
    """Feed per-tick durations; exports a tick-duration histogram and a
    straggler-tick counter into ``registry``."""

    def __init__(self, registry: Registry, *, window: int = 64,
                 threshold: float = 3.0, min_ticks: int = 8,
                 log_every: int = 32):
        self.threshold = threshold
        self.min_ticks = min_ticks
        self.log_every = max(1, log_every)
        self._suppressed = 0
        self._window: deque = deque(maxlen=window)
        self.tick_seconds = registry.histogram(
            "serving_tick_seconds", buckets=TICK_BUCKETS,
            help="Wall time of one PagedServer.step() tick")
        self.straggler_ticks = registry.counter(
            "serving_straggler_ticks",
            help="Ticks exceeding threshold x rolling median")

    def on_tick(self, dur_s: float) -> bool:
        """Record one tick; returns True when the tick was flagged as a
        straggler against the rolling median."""
        self.tick_seconds.observe(dur_s)
        flagged_tick = False
        if len(self._window) >= self.min_ticks:
            med = median(self._window)
            if med > 0 and dur_s > self.threshold * med:
                flagged_tick = True
                self.straggler_ticks.inc()
                if self._suppressed == 0:
                    logger.warning(
                        "straggler tick: %.2f ms > %.1fx rolling median "
                        "%.2f ms (next %d flags logged at debug)",
                        dur_s * 1e3, self.threshold, med * 1e3,
                        self.log_every - 1)
                else:
                    logger.debug(
                        "straggler tick: %.2f ms > %.1fx rolling median "
                        "%.2f ms", dur_s * 1e3, self.threshold, med * 1e3)
                self._suppressed = (self._suppressed + 1) % self.log_every
        self._window.append(dur_s)
        return flagged_tick
