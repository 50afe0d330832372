"""The harness end to end at a test size on the CPU, without a chip.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

A float32 model of two layers (``tiny.json``) under a short chat mix
(``tiny-chat.json``) runs through the same set-up, open-loop window and
check as a cell does.  A sound run reads ``correct``; a run with a
fault of ``bench/faults.py`` planted under the timed path (served
tokens altered where the server produces them, or GRIFFIN decoding
with neurons another prompt chose) does not; the control (the reference
with int8 weights in the program's place) reads above the limit that
the sound run stays under.
"""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import faults  # noqa: E402
from bench import harness  # noqa: E402
from bench import spec as spec_lib  # noqa: E402

HERE = Path(__file__).resolve().parent
SECONDS = 2.0


def tiny_cell(tier: float = 0.5) -> spec_lib.Cell:
    c = spec_lib.load_json(HERE / "tiny.json")
    t = spec_lib.load_json(HERE / "tiny-chat.json")
    t["tiers"] = {str(tier): 1.0}
    b = spec_lib.benchmark()
    arch = spec_lib.yardstick(c)
    return spec_lib.Cell(
        name="tiny", chips=1, config=c, traffic=t, arch=arch,
        spec=arch.ModelSpec.from_file(c),
        per_layer=[m for m in b["per_layer"]
                   if m["source"] != "device_trace"],
        end_to_end=b["end_to_end"])


@pytest.fixture(scope="module", autouse=True)
def _jax():
    harness.setup_jax()


@pytest.mark.parametrize("tier", [0.5, 1.0])
def test_sound_run_is_correct(tier):
    cell = tiny_cell(tier)
    run, res = harness.run_cell(cell, 2**33 + 5, SECONDS, False,
                                time.perf_counter())
    assert res["correct"], res
    assert run.compiles_in_window == [], run.compiles_in_window
    e2e = harness.end_to_end(run)
    assert all(np.isfinite(v) and v > 0 for v in e2e.values()), e2e
    assert all(v is not None for v in harness.per_layer(run).values())


def test_altered_token_is_not_correct():
    cell = tiny_cell(0.5)
    _, res = harness.run_cell(cell, 2**33 + 6, SECONDS, False,
                              time.perf_counter(),
                              tamper=faults.altered_token)
    assert not res["correct"], res


@pytest.mark.parametrize("fault", ["stale_selection", "foreign_selection"])
def test_wrong_selection_is_not_correct(fault):
    cell = tiny_cell(0.5)
    _, res = harness.run_cell(cell, 2**33 + 8, SECONDS, False,
                              time.perf_counter(),
                              tamper=faults.FAULTS[fault])
    assert not res["correct"], res


def test_control_reads_above_the_limit():
    """Runs the reference with int8 weights where the program was, on
    the same requests, and reads the gap of its first-ranked tokens."""
    cell = tiny_cell(0.5)
    run, res = harness.run_cell(cell, 2**33 + 7, SECONDS, False,
                                time.perf_counter())
    assert res["correct"]
    w = cell.arch.make(cell.spec, harness.seed_key(2**33 + 7))
    sample = harness.check_sample(run, 10**6, 10**6)
    readings = harness.gap_readings(cell, w, sample, control=True)[0.5]
    for name, limits in cell.config["check"]["limits"].items():
        assert readings["served"][name] <= limits["0.5"]
        assert readings["control"][name] > limits["0.5"], readings
