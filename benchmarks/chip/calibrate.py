"""Readings that set a cell's rate, its arrival order and its limits.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        sweep --rates 1.4,1.8,2.2 --seconds 20
    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        orders --service-ms 488.5 --seconds 51
    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        readings --seeds 1,2,...,12 --control 3 --seconds 10
    python3 benchmarks/chip/calibrate.py --config <file.json> \\
        --traffic <name> readings ...   # a configuration not yet in a cell

``sweep`` serves the cell's open loop at each rate in turn (one process,
one compile) and prints the latencies and how far the backlog had grown
when the window closed: the knee is the highest rate whose backlog does
not grow. ``orders`` needs no chip: it serves the open loop's arrivals,
in the order of each ``schedule_seed`` from 0 to ``--orders``, through a
model of the batch-1 server (one request at a time, ``--service-ms``
each, first come first served) and prints where the traffic file's order
falls among them, and the order whose p50 and p90 lie nearest the
medians over all orders. ``readings`` serves a short window at the cell's own traffic
for each seed and prints every number of ``check.NUMBERS`` for the
program against the reference on the run's own sample, and for the first
``--control`` seeds the control's numbers on the same requests. Each
result is one JSON line on standard output."""
import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import check, harness  # noqa: E402
from benchmarks.chip import traffic as traffic_lib  # noqa: E402


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def sweep(cell, rates, seconds, seed) -> None:
    for rate in rates:
        tr = dict(cell.traffic, rate_per_s=rate)
        c = dataclasses.replace(cell, traffic=tr)
        run, _, compiles = harness.serve_window(c, seed, seconds, False,
                                                t_start=time.perf_counter())
        lat = run.latencies_s()
        backlog = sum(1 for r in run.recs
                      if not (r.done and r.t_done <= run.t1))
        emit({"rate_per_s": rate, "requests": len(run.recs),
              "p50_ms": float(np.percentile(lat, 50) * 1e3),
              "p90_ms": float(np.percentile(lat, 90) * 1e3),
              "unfinished_at_close": backlog,
              "completed_per_s": run.images_per_s(),
              "compiles": compiles})


def orders(traffic, seconds, service_s, n_orders) -> None:
    def tails(order):
        due = traffic_lib.open_schedule(dict(traffic, schedule_seed=order),
                                        seconds)
        free, lat = 0.0, []
        for t in due:
            free = max(t, free) + service_s
            lat.append(free - t)
        return np.percentile(lat, 50) * 1e3, np.percentile(lat, 90) * 1e3

    got = np.array([tails(s) for s in range(n_orders)])
    med = np.median(got, axis=0)
    nearest = int(np.argmin(np.max(np.abs(got / med - 1), axis=1)))
    own = tails(int(traffic["schedule_seed"]))
    emit({"orders": n_orders, "median_p50_ms": med[0],
          "median_p90_ms": med[1],
          "p90_quartiles_ms": list(np.percentile(got[:, 1], [25, 75])),
          "file_order": int(traffic["schedule_seed"]), "file_p50_ms": own[0],
          "file_p90_ms": own[1],
          "file_p90_rank": float(np.mean(got[:, 1] < own[1])),
          "nearest_order": nearest, "nearest_p50_ms": got[nearest, 0],
          "nearest_p90_ms": got[nearest, 1]})


def readings(cell, seeds, n_control, seconds) -> None:
    m = cell.model
    for i, seed in enumerate(seeds):
        run, outputs, compiles = harness.serve_window(
            cell, seed, seconds, False, t_start=time.perf_counter())
        idx = check.pick(outputs, check.CHECK_REQUESTS, seed)
        images = [outputs[j][0] for j in idx]
        t = time.perf_counter()
        refs = check.reference_outputs(m, seed, images)
        t_ref = time.perf_counter() - t
        rec = {"seed": seed, "requests": len(run.recs), "checked": len(idx),
               "compiles": compiles, "reference_s": t_ref,
               "program": check.compare([outputs[j][1:] for j in idx], refs)}
        if i < n_control:
            ctrl = check.reference_outputs(m, seed, images, control=True)
            rec["control"] = check.compare(ctrl, refs)
        emit(rec)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload",
                    help="a cell of BENCHMARK.json")
    ap.add_argument("--config",
                    help="or a configuration file ...")
    ap.add_argument("--traffic", help="... under a traffic mix")
    sub = ap.add_subparsers(dest="mode", required=True)
    sw = sub.add_parser("sweep")
    sw.add_argument("--rates", required=True)
    sw.add_argument("--seconds", type=float, default=20.0)
    sw.add_argument("--seed", type=int, default=1)
    od = sub.add_parser("orders")
    od.add_argument("--service-ms", type=float, required=True)
    od.add_argument("--seconds", type=float, required=True)
    od.add_argument("--orders", type=int, default=2000)
    rd = sub.add_parser("readings")
    rd.add_argument("--seeds", required=True)
    rd.add_argument("--control", type=int, default=3)
    rd.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    if args.workload:
        cell = harness.load_cell(args.workload)
    else:
        cell = harness.make_cell(f"{Path(args.config).stem}.{args.traffic}",
                                 Path(args.config), args.traffic)
    if args.mode == "sweep":
        sweep(cell, [float(r) for r in args.rates.split(",")], args.seconds,
              args.seed)
    elif args.mode == "orders":
        orders(cell.traffic, args.seconds, args.service_ms / 1e3,
               args.orders)
    else:
        readings(cell, [int(s) for s in args.seeds.split(",")],
                 args.control, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
