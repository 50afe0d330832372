"""The yardstick a configuration names, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Parity: ``testdata/llama_parity.json`` holds what the benchmark computed
from the Llama block's shapes before that code moved into
``bench/arch/llama.py``: a digest of every weight leaf from one seed at
the test size (``tiny.json``), the reference's gaps and its int8
control's at tier 0.5 and 1.0 over one token sequence, GRIFFIN's kept
count, ``token_flops`` and the paged-attention call's counts over a
grid, for ``tiny.json`` and ``yi-9b-8L``, and ``tick_mfu`` and
``paged_attn_roofline`` read on hand-made plans and the recorded trace
``testdata/small.xplane.pb``.  The ``llama`` yardstick reproduces all
of it exactly (the gaps bit for bit, on the CPU).  The file is what
``record_premove_parity.py`` writes from a checkout of the code before
the move.  To record the readings of the tree as it stands:

    JAX_PLATFORMS=cpu python3 bench/tests/test_yardstick.py <out.json>

Second architecture: ``tied/`` is a benchmark root's ``bench/``
directory with a configuration, a traffic mix and a yardstick of its
own (``tied/arch/tied.py``: tied embeddings, other sizes, weight names
and program tree).  Copied beside a ``BENCHMARK.json`` that names it, it
goes through ``spec.cell``, ``harness.build_server``, a served request
and the check's reference with no change to ``bench/``.
"""
import dataclasses
import hashlib
import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import harness, trace_reduce  # noqa: E402
from bench import spec as spec_lib  # noqa: E402

HERE = Path(__file__).resolve().parent
PARITY = ROOT / "bench" / "testdata" / "llama_parity.json"
SMALL = ROOT / "bench" / "testdata" / "small.xplane.pb"

SEED = 2**33 + 21
TIERS = (0.25, 0.5, 1.0)
CTX = (1, 7, 100, 2048)
POS = (0, 1, 37, 1000)
ROWS = (1, 8, 32)
N_PROMPT, N_TOTAL = 40, 60
EPOCH = 1000.0
#: (seconds after the epoch, prefill (start, tokens), decode rows
#: (position, tier)), as ``harness.record_plans`` keeps them
PLANS = [
    (1.1, (0, 32), [(100, 0.5), (200, 0.5)]),
    (1.2, None, [(101, 0.5), (201, 0.5), (5, 1.0)]),
    (1.3, (32, 17), []),
    (1.4, None, [(7, 1.0)]),
]
#: ``tick`` spans: (seconds after the epoch, ms)
TICKS = [(1.1, 12.0), (1.2, 9.5), (1.3, 4.0), (1.4, 8.25)]
READERS = ("tick_mfu", "paged_attn_roofline")


def tiny_cell() -> spec_lib.Cell:
    c = spec_lib.load_json(HERE / "tiny.json")
    b = spec_lib.benchmark()
    arch = spec_lib.yardstick(c)
    return spec_lib.Cell(
        name="tiny", chips=1, config=c,
        traffic=spec_lib.load_json(HERE / "tiny-chat.json"), arch=arch,
        spec=arch.ModelSpec.from_file(c), per_layer=b["per_layer"],
        end_to_end=b["end_to_end"])


def counts(cell) -> dict:
    arch, s = cell.arch, cell.spec
    attn = arch.KERNEL_COUNTS["paged_attn"]
    return {
        "keep": {str(t): arch.keep(s, t) for t in TIERS},
        "token_flops": [[ctx, t, arch.token_flops(s, ctx, arch.keep(s, t))]
                        for ctx in CTX for t in TIERS],
        "attn_call": [[p, n, *attn(s, p, n)] for p in POS for n in ROWS],
    }


def traced_run(cell, reduced) -> harness.RunData:
    """A traced run's readings as the readers see them: the plans, the
    ticks' spans and the reduced device trace."""
    events = [{"ph": "X", "name": "tick", "cat": "step", "pid": 1,
               "tid": 1, "ts": t * 1e6, "dur": ms * 1e3} for t, ms in TICKS]
    return harness.RunData(
        cell=cell, seed=0, t_open=EPOCH + 1.0, t_close=EPOCH + 2.0,
        records=[], setup_s=0.0, compiles_in_window=[], peak_bytes=0,
        device={"kind": "TPU v5 lite"}, tracer_events=events,
        tracer_epoch=EPOCH,
        plans=[(EPOCH + t, pf, dec) for t, pf, dec in PLANS], trace=reduced)


def small_trace():
    return trace_reduce.reduce(trace_reduce.load(SMALL),
                               {"paged_attn": ["paged_attn"]})


def read(cell, name):
    return spec_lib.metric_module(name).read(traced_run(cell, small_trace()))


def weight_digests(w) -> dict:
    return {k: {"shape": list(v.shape), "dtype": str(v.dtype),
                "sha256": hashlib.sha256(np.asarray(v).tobytes()).hexdigest()}
            for k, v in sorted(w.items())}


def gap_readings(cell, w) -> dict:
    import jax.numpy as jnp

    arch, s = cell.arch, cell.spec
    toks = np.random.default_rng(7).integers(0, s.vocab, N_TOTAL)
    seq = np.zeros((cell.config["serving"]["max_len"],), np.int32)
    seq[:N_TOTAL] = toks
    out = {}
    for tier in (0.5, 1.0):
        k = None if tier >= 1.0 else arch.keep(s, tier)
        served, ctl = arch.gaps(s, w, jnp.asarray(seq), jnp.int32(N_PROMPT),
                                jnp.int32(N_TOTAL), k=k, control=True)
        out[str(tier)] = {"served": [float(x) for x in np.asarray(served)],
                          "control": [float(x) for x in np.asarray(ctl)]}
    return [int(x) for x in toks], out


def readings() -> dict:
    """Everything the parity file records, from the tree as it stands."""
    cell, yi = tiny_cell(), spec_lib.cell("yi9b-t50-chat")
    w = cell.arch.make(cell.spec, harness.seed_key(SEED))
    tokens, gaps = gap_readings(cell, w)
    reduced = small_trace()
    return {
        "seed": SEED, "weights": weight_digests(w), "tokens": tokens,
        "n_prompt": N_PROMPT, "gaps": gaps,
        "counts": {"tiny": counts(cell), "yi-9b-8L": counts(yi)},
        "readers": {
            name: {n: spec_lib.metric_module(n).read(traced_run(c, reduced))
                   for n in READERS}
            for name, c in (("tiny", cell), ("yi-9b-8L", yi))},
    }


@pytest.fixture(scope="module")
def recorded():
    return json.loads(PARITY.read_text())


@pytest.fixture(scope="module")
def now():
    return readings()


@pytest.mark.parametrize("part", ["seed", "weights", "tokens", "gaps",
                                  "counts", "readers"])
def test_llama_yardstick_reproduces_the_recording(recorded, now, part):
    assert now[part] == recorded[part]


def test_configurations_name_their_yardstick():
    for path in sorted((ROOT / "bench" / "configs").glob("*.json")):
        c = spec_lib.load_json(path)
        assert (ROOT / "bench" / "arch" / f"{c['yardstick']}.py").is_file()
    c = dict(spec_lib.load_json(HERE / "tiny.json"))
    del c["yardstick"]
    with pytest.raises(KeyError, match="yardstick"):
        spec_lib.yardstick(c)


@pytest.mark.parametrize("name", READERS)
def test_readers_take_counts_from_the_cell(recorded, name):
    """The readers give the recorded values through the cell's module,
    and twice them from a module whose counts are doubled."""
    cell = tiny_cell()
    assert read(cell, name) == recorded["readers"]["tiny"][name]
    arch = cell.arch
    attn = arch.KERNEL_COUNTS["paged_attn"]
    doubled = SimpleNamespace(
        keep=arch.keep,
        token_flops=lambda s, ctx, k: 2 * arch.token_flops(s, ctx, k),
        KERNEL_COUNTS={"paged_attn": lambda s, p, n: tuple(
            2 * c for c in attn(s, p, n))})
    got = read(dataclasses.replace(cell, arch=doubled), name)
    assert got == pytest.approx(2 * recorded["readers"]["tiny"][name],
                                rel=1e-12)


@pytest.fixture(scope="module")
def tied_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tied_root")
    shutil.copytree(HERE / "tied", root / "bench")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "tied-chat", "config": "tied",
                       "traffic": "tied-chat", "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    return root


def test_a_kernel_the_yardstick_does_not_count_reads_nothing(tied_root):
    cell = spec_lib.cell("tied-chat", tied_root)
    assert "paged_attn" not in cell.arch.KERNEL_COUNTS
    assert read(cell, "paged_attn_roofline") is None
    assert read(cell, "tick_mfu") > 0


def test_second_architecture_serves_and_is_checked(tied_root):
    """A request served from the toy's weights through the harness's
    set-up, then the check's reference call on it: ``correct``; with one
    served token changed, not."""
    cell = spec_lib.cell("tied-chat", tied_root)
    assert cell.arch is not tiny_cell().arch
    seed = 2**33 + 23
    w, srv = harness.build_server(cell, seed)
    assert srv.cfg.tie_embeddings
    prompt = np.random.default_rng(3).integers(
        0, cell.spec.vocab, 20).astype(np.int32)
    rid = srv.submit(prompt, max_new=12, tier=1.0)
    out = srv.drain()
    rec = harness.Record(due=0.0, tier=1.0, prompt=prompt, max_new=12,
                         rid=rid, tokens=[int(t) for t in out[rid]],
                         finished=True)
    run = harness.RunData(cell=cell, seed=seed, t_open=0.0, t_close=1.0,
                          records=[rec], setup_s=0.0, compiles_in_window=[],
                          peak_bytes=0, device={})
    ok, numbers, desc = harness.check(run, w)
    assert ok, (numbers, desc)
    rec.tokens[5] = (rec.tokens[5] + 1) % cell.spec.vocab
    ok, numbers, _ = harness.check(run, w)
    assert not ok, numbers


if __name__ == "__main__":
    rec = readings()
    Path(sys.argv[1]).write_text("{\n" + ",\n".join(
        f" {json.dumps(k)}: {json.dumps(v)}" for k, v in rec.items()) + "\n}\n")
