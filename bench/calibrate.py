"""Readings that the ``mean_logit_gap`` limits are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --faults foreign_selection --seconds 20

For each seed, in one process: the cell's weights and traffic from that
seed, the program served at the cell's own load (lead-in, then a short
window long enough to finish the mix's longest requests), then the same
sample of finished requests a run compares.  Each seed gives the
program's numbers; on the control seeds the same requests also give
the control's (the reference in the program's place with int8 weights,
read at the token that it ranks first) and how far the neurons GRIFFIN
keeps for two different prompts of the sample overlap.  Each fault of
``bench/faults.py`` named in ``--faults`` is served again on every seed
with that fault planted, and read the same way.  Prints one JSON line per
reading, and writes them to ``--out``.  Needs a TPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def serve(cell, seed, seconds, fault):
    """The cell served from ``seed`` with ``fault`` planted (or none);
    returns the weights and the check's sample, the server freed."""
    from bench import faults, harness

    w, srv = harness.build_server(cell, seed)
    harness.warm_up(srv, cell)
    if fault:
        faults.FAULTS[fault](srv)
    t0 = time.perf_counter()
    records = harness.make_records(cell, seed, seconds, t0)
    loop = harness.Loop(srv, records)
    loop.run_until(t0 + float(cell.traffic["lead_s"]) + seconds)
    for r in records:
        r.req = None
    del srv, loop
    gc.collect()
    run = harness.RunData(cell=cell, seed=seed, t_open=t0, t_close=t0,
                          records=records, setup_s=0.0,
                          compiles_in_window=[], peak_bytes=0, device={})
    chk = cell.config["check"]
    return w, harness.check_sample(run, int(chk["sample_tokens"]),
                                   int(chk["sample_requests"]))


def kept_overlap(cell, w, sample):
    """Mean over the sample's prompt pairs and the layers of the share
    of kept neurons that two prompts have in common, per tier."""
    import jax.numpy as jnp

    arch, T = cell.arch, cell.config["serving"]["max_len"]
    out = {}
    for tier in sorted({r.tier for r in sample if r.tier < 1.0}):
        k = arch.keep(cell.spec, tier)
        masks = []
        for r in (r for r in sample if r.tier == tier):
            seq = np.zeros((T,), np.int32)
            seq[: len(r.prompt)] = r.prompt
            masks.append(np.asarray(arch.kept(
                cell.spec, w, jnp.asarray(seq), jnp.int32(len(r.prompt)),
                k)))
        pairs = [float(((a & b).sum(-1) / k).mean())
                 for a, b in itertools.combinations(masks, 2)]
        if pairs:
            out[f"t{tier}"] = {"mean": float(np.mean(pairs)),
                               "min": float(np.min(pairs)),
                               "max": float(np.max(pairs)),
                               "pairs": len(pairs)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench import spec as spec_lib

    cell = spec_lib.cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.setup_jax()
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    faults = [f for f in args.faults.split(",") if f]
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        for fault in [None] + faults:
            t_start = time.perf_counter()
            w, sample = serve(cell, seed, args.seconds, fault)
            control = fault is None and seed in controls
            row = {"seed": seed, "fault": fault}
            for tier, d in harness.gap_readings(cell, w, sample,
                                                control=control).items():
                row[f"t{tier}"] = dict(d["served"], tokens=d["tokens"],
                                       requests=d["requests"])
                if control:
                    row[f"t{tier}_int8"] = d["control"]
            if control:
                row["kept_overlap"] = kept_overlap(cell, w, sample)
            row["longest"] = max(len(r.prompt) + len(r.tokens)
                                 for r in sample)
            row["seconds"] = time.perf_counter() - t_start
            rows.append(row)
            print(json.dumps(row), flush=True)
            del w, sample
            gc.collect()
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
