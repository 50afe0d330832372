"""Records ``testdata/llama_parity.json`` from a checkout of the
benchmark as it was before its Llama code moved into
``bench/arch/llama.py``: ``bench/spec.py`` ``ModelSpec``,
``bench/weights.py``, ``bench/reference.py`` and ``bench/flops.py``.

    git archive 50b0a76 | tar -x -C <dir>
    JAX_PLATFORMS=cpu python3 bench/tests/record_premove_parity.py <dir> <out.json>

It calls only that checkout's code.  The sizes, seed, plans and ticks
are those of ``test_yardstick.py``, which reads the same quantities
through the ``llama`` yardstick and compares them with the file.
"""
import hashlib
import json
import sys
from pathlib import Path

ROOT = Path(sys.argv[1]).resolve()
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import flops, harness, reference, trace_reduce, weights  # noqa: E402
from bench import spec as spec_lib  # noqa: E402

# as in test_yardstick.py
SEED = 2**33 + 21
TIERS = (0.25, 0.5, 1.0)
CTX = (1, 7, 100, 2048)
POS = (0, 1, 37, 1000)
ROWS = (1, 8, 32)
N_PROMPT, N_TOTAL = 40, 60
EPOCH = 1000.0
PLANS = [
    (1.1, (0, 32), [(100, 0.5), (200, 0.5)]),
    (1.2, None, [(101, 0.5), (201, 0.5), (5, 1.0)]),
    (1.3, (32, 17), []),
    (1.4, None, [(7, 1.0)]),
]
TICKS = [(1.1, 12.0), (1.2, 9.5), (1.3, 4.0), (1.4, 8.25)]
READERS = ("tick_mfu", "paged_attn_roofline")


def tiny_cell():
    c = spec_lib.load_json(ROOT / "bench/tests/tiny.json")
    b = spec_lib.benchmark()
    return spec_lib.Cell(
        name="tiny", chips=1, config=c,
        traffic=spec_lib.load_json(ROOT / "bench/tests/tiny-chat.json"),
        spec=spec_lib.ModelSpec.from_file(c), per_layer=b["per_layer"],
        end_to_end=b["end_to_end"])


def counts(s):
    return {
        "keep": {str(t): flops.tier_k(s, t) for t in TIERS},
        "token_flops": [[ctx, t, flops.token_flops(s, ctx, flops.tier_k(s, t))]
                        for ctx in CTX for t in TIERS],
        "attn_call": [[p, n, *flops.attn_call(s, p, n)]
                      for p in POS for n in ROWS],
    }


def readers(cell, reduced):
    events = [{"ph": "X", "name": "tick", "cat": "step", "pid": 1,
               "tid": 1, "ts": t * 1e6, "dur": ms * 1e3} for t, ms in TICKS]
    run = harness.RunData(
        cell=cell, seed=0, t_open=EPOCH + 1.0, t_close=EPOCH + 2.0,
        records=[], setup_s=0.0, compiles_in_window=[], peak_bytes=0,
        device={"kind": "TPU v5 lite"}, tracer_events=events,
        tracer_epoch=EPOCH,
        plans=[(EPOCH + t, pf, dec) for t, pf, dec in PLANS], trace=reduced)
    return {n: spec_lib.metric_module(n).read(run) for n in READERS}


def main(out):
    cell, yi = tiny_cell(), spec_lib.cell("yi9b-t50-chat")
    s = cell.spec
    w = weights.make(s, weights.seed_key(SEED))
    toks = np.random.default_rng(7).integers(0, s.vocab, N_TOTAL)
    seq = np.zeros((cell.config["serving"]["max_len"],), np.int32)
    seq[:N_TOTAL] = toks
    gaps = {}
    for tier in (0.5, 1.0):
        k = None if tier >= 1.0 else flops.tier_k(s, tier)
        served, ctl = reference.gaps(s, w, jnp.asarray(seq),
                                     jnp.int32(N_PROMPT), jnp.int32(N_TOTAL),
                                     k=k, control=True)
        gaps[str(tier)] = {"served": [float(x) for x in np.asarray(served)],
                           "control": [float(x) for x in np.asarray(ctl)]}
    reduced = trace_reduce.reduce(
        trace_reduce.load(ROOT / "bench/testdata/small.xplane.pb"),
        {"paged_attn": ["paged_attn"]})
    rec = {
        "seed": SEED,
        "weights": {k: {"shape": list(v.shape), "dtype": str(v.dtype),
                        "sha256": hashlib.sha256(
                            np.asarray(v).tobytes()).hexdigest()}
                    for k, v in sorted(w.items())},
        "tokens": [int(x) for x in toks], "n_prompt": N_PROMPT, "gaps": gaps,
        "counts": {"tiny": counts(s), "yi-9b-8L": counts(yi.spec)},
        "readers": {"tiny": readers(cell, reduced),
                    "yi-9b-8L": readers(yi, reduced)},
    }
    Path(out).write_text("{\n" + ",\n".join(
        f" {json.dumps(k)}: {json.dumps(v)}" for k, v in rec.items()) + "\n}\n")


if __name__ == "__main__":
    main(sys.argv[2])
