"""The trace reduction on a small trace recorded on a TPU v5e.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

``testdata/small.xplane.pb`` comes from ``record_trace.py``: inside a
``bench_window`` annotation a matmul, a 50 ms host sleep, one fused
paged-attention call, and the matmul again.
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as T  # noqa: E402

SMALL = ROOT / "bench" / "testdata" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    tr = T.load(SMALL)
    return tr, T.reduce(tr, {"paged_attn": ["paged_attn"],
                             "absent": ["no_such_kernel"]})


def test_window_is_the_annotation(reduced):
    tr, r = reduced
    assert len(tr.host[T.WINDOW_ANNOTATION]) == 1
    assert 0.05 <= r.window_s < 0.08
    assert r.n_devices == 1


def test_busy_and_the_sleep_gap(reduced):
    _, r = reduced
    assert 0 < r.busy_s < 0.005
    longest = max(b - a for a, b in r.gaps) * 1e-9
    assert 0.049 <= longest <= r.window_s
    idle = sum(b - a for a, b in r.gaps) * 1e-9
    assert abs(idle + r.busy_s - r.window_s) < 1e-6


def test_kernel_time_by_pattern(reduced):
    _, r = reduced
    assert r.kernel_calls == {"paged_attn": 1, "absent": 0}
    assert 0 < r.kernel_seconds["paged_attn"] < r.busy_s
    assert r.kernel_seconds["absent"] == 0


def test_union_and_gap_names():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    gaps = [(0.0, 10e6), (20e6, 21e6)]
    spans = [("tick", 0.0, 30e6), ("decode", 4e6, 6e6)]
    named = T.name_gaps(gaps, spans)
    assert named[0] == ["decode", 0.01]
    assert named[1][0] == "tick"
    assert T.name_gaps([(0.0, 1.0)], [])[0][0] == "no host span"
