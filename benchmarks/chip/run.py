"""Run one cell of the on-chip benchmark once, in this process:

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything the cell needs is found by its name in BENCHMARK.json and under
benchmarks/chip/.  The last line of standard output is the result object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number compared
with its limit); the last lines of standard error are the same checks.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 1.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from benchmarks.chip import check, harness, spec

    def log(msg):
        print(f"bench: {msg}", file=sys.stderr, flush=True)

    try:
        cell = spec.load_cell(args.workload, root)
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START, log=log)
    except harness.NoChip as e:
        log(f"FAIL: {e}")
        return 1
    for line in check.lines(out["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
