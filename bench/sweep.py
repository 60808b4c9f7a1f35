"""Find an open-loop cell's knee: the highest offered rate the system
sustains without a growing backlog.  Run once, by hand, on the chip;
the cell then fixes its rate (at about 0.8 of the knee).

    PYTHONPATH=src python3 bench/sweep.py <workload> --rates 0.6 1.0 1.4 \\
        --seconds 30 --seed 1

For each rate, one whole run of the cell at that rate in this process.
It prints per rate: requests due in the window and served by its end,
the queue at the window's start and end, tokens/s and the TTFT p90.
A rate is sustained when the queue at the end is no longer than at the
start plus a few requests and the window served what arrived in it.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    import catalog
    import harness
    base = catalog.Catalog(ROOT).cell(args.workload)
    for rate in args.rates:
        cell = copy.deepcopy(base)
        cell.settings["rate"] = rate
        res = harness.run(ROOT, args.workload, args.seed, args.seconds,
                          False, t_process=time.perf_counter(), cell=cell)
        r = res["run"]
        due = [x for x in r.records if r.t0 <= x.due < r.t1]
        first = [x for x in due if x.times and x.times[0] <= r.t1]
        done = [x for x in due if x.req is not None and x.req.done
                and x.times and x.times[-1] <= r.t1]
        row = {"rate": rate, "due_in_window": len(due),
               "first_token_in_window": len(first),
               "finished_in_window": len(done),
               "queue_at_start": r.steps[0].queued if r.steps else None,
               "queue_at_end": r.steps[-1].queued if r.steps else None,
               "seats_busy_mean": (sum(s.seats_busy for s in r.steps)
                                   / max(1, len(r.steps))),
               "metrics": res["line"]["metrics"]}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
