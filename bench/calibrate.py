"""Readings for a cell's correctness limit: the program's and the
control's, over many seeds, in one process.

    PYTHONPATH=src python3 bench/calibrate.py <workload> --seeds 1 2 3 \\
        --seconds 20

For each seed it makes a whole run of the cell (set-up, a window of
``--seconds`` at the cell's load, the check) and, on the same prompts
and served tokens, the control: the reference computed with every
matmul input in float8 (``bench/reference``).  The control's reading
goes through the harness's own comparison (``harness.verdict``) against
the cell's limit, so each line shows ``control_correct``, which has to
be false.  It prints one JSON line per seed and a summary: the
program's largest reading (the limit's lower end), the control's
smallest (its upper end), and whether every program run was correct
and every control run was not.  The benchmark's own runs never run the
control.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    import harness
    rows = []
    for seed in args.seeds:
        t = time.perf_counter()
        res = harness.run(ROOT, args.workload, seed, args.seconds, False,
                          t_process=t, control=True)
        g = res["gaps"] or {}
        row = {"seed": seed, "correct": res["line"]["correct"],
               "control_correct": res["control"]["correct"],
               "tokens": g.get("tokens"), "max_gap": g.get("max_gap"),
               "mean_gap": g.get("mean_gap"),
               "control_max_gap": g.get("control_max_gap"),
               "control_mean_gap": g.get("control_mean_gap"),
               "metrics": res["line"]["metrics"],
               "memory_peak_bytes": res["line"]["device"][
                   "memory_peak_bytes"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    prog = [r["max_gap"] for r in rows if r["max_gap"] is not None]
    ctrl = [r["control_max_gap"] for r in rows
            if r["control_max_gap"] is not None]
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "program_max": max(prog) if prog else None,
                      "control_min": min(ctrl) if ctrl else None,
                      "program_all_correct": all(r["correct"] for r in rows),
                      "control_any_correct": any(r["control_correct"]
                                                 for r in rows)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
