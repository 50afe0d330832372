"""Find a cell's knee: the highest offered rate the server sustains.

    python3 bench/sweep.py --workload <cell> --rates 0.5,1,1.5 --seconds 30

One process, one server, warmed once; each rate runs the cell's mix at
that rate (lead-in, then the window) and reports the waiting queue's
growth over the window (a least-squares slope of its length per tick,
in requests per second) beside the end-to-end metrics.  The knee is the
highest rate whose queue does not grow.  Between rates every live
request is cancelled and the prefix cache flushed.  Writes the readings
as JSON to ``--out`` as well.  Needs a TPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench import spec as spec_lib

    cell = spec_lib.cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    harness.setup_jax()
    _, srv = harness.build_server(cell, args.seed)
    harness.warm_up(srv, cell)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.traffic, rate_rps=rate)
        c = dataclasses.replace(cell, traffic=mix)
        t0 = time.perf_counter()
        records = harness.make_records(c, args.seed, args.seconds, t0)
        queue = []

        def step():
            srv.step()
            queue.append((time.perf_counter(), len(srv.sched.queue)))

        loop = harness.Loop(srv, records, on_step=step)
        loop.run_until(t0 + float(mix["lead_s"]))
        t_open = time.perf_counter()
        loop.run_until(t_open + args.seconds)
        run = harness.RunData(
            cell=c, seed=args.seed, t_open=t_open,
            t_close=t_open + args.seconds, records=records, setup_s=0.0,
            compiles_in_window=[], peak_bytes=0, device={})
        e2e = harness.end_to_end(run)
        win = [(t, n) for t, n in queue if t >= t_open]
        ts = np.array([t for t, _ in win])
        ns = np.array([n for _, n in win], np.float64)
        slope = float(np.polyfit(ts - ts[0], ns, 1)[0]) if len(ts) > 2 \
            else float("nan")
        due = sum(run.in_window(r.due) for r in records)
        row = {"rate_rps": rate, "due_in_window": due,
               "queue_at_open": int(ns[0]) if len(ns) else 0,
               "queue_at_close": int(ns[-1]) if len(ns) else 0,
               "queue_slope_per_s": slope, **e2e}
        row.pop("setup_s")
        rows.append(row)
        print(json.dumps(row), flush=True)
        for r in records:
            if r.rid >= 0 and not (r.finished or r.aborted):
                srv.cancel(r.rid)
        srv.drain()
        srv.sched.flush_prefix()
        srv.sched.finished.clear()
        srv.reset_metrics()
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
