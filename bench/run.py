"""Run one benchmark cell on the chip this process finds.

    PYTHONPATH=src python3 bench/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics read from a profiler trace of the window.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
then ``check``, the numbers compared with the reference beside their
limits; the same numbers end standard error.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.
The program under test (``src/``) is found beside this directory;
``PYTHONPATH`` need not name it.
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
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"[bench] no program under test at {ROOT}/src/repro",
              file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    import harness
    try:
        res = harness.run(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    line = res["line"]
    for name, c in line["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
