"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics
are found by name from ``BENCHMARK.json`` and ``bench/``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (from a profiled run).  The last
line of standard output is one JSON object; the last lines of standard
error are the numbers ``correct`` compared, each with its limit.

Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec as spec_lib

    cell = spec_lib.cell(args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"bench: needs {cell.chips} TPU chip(s); JAX finds "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 2
    from bench import harness
    from bench.peaks import peaks

    peaks(devs[0].device_kind)  # an unknown chip is an error
    harness.setup_jax()
    run, res = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), T_PROCESS)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": run.peak_bytes}
    run.device = device
    log(f"[setup] {run.setup_s:.3f} s; compiles so far "
        f"{harness.compiles()[0]} ({harness.compiles()[1]:.1f} s)")
    log(f"[window] compiles in window: {len(run.compiles_in_window)} "
        f"(programs built, compiled or loaded from the cache: "
        f"{run.compiles_in_window})")
    log(f"[generator] lateness p99 {res['lateness_p99_ms']:.3f} ms "
        f"(submit - due, requests due in the window)")
    due = [r for r in run.records if run.in_window(r.due)]
    attempted = len(due)
    failed = sum(r.aborted for r in due)
    if args.trace:
        values = harness.per_layer(run)
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        tr = run.trace
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        log(f"[trace] busy {tr.busy_s:.6f} s of {tr.window_s:.6f} s; "
            f"kernel calls {tr.kernel_calls}, seconds {tr.kernel_seconds}")
    else:
        values = harness.end_to_end(run)
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = {k: v for k, v in values.items() if k in units}
    metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
    for k, v in values.items():
        log(f"[metric] {k} = {v} {units[k]}")
    log(f"[check] {res['check_desc']}")
    for k, d in res["check"].items():
        log(f"[check] {k} = {d['value']} (limit {d['limit']})")
    out = {"correct": bool(res["correct"]), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if args.trace and run.breakdown is not None:
        out["breakdown"] = run.breakdown
    out["check"] = res["check"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
