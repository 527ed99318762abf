"""The readings a cell's limits are set from, on the chip at the cell's own
size, many seeds in one process (set-up is long):

    python3 benchmarks/chip/readings.py --workload <cell> --what sound \\
        --seeds 1 2 3 [--seconds 0] [--keep-trace DIR]

``--what`` is ``sound`` (the program as the benchmark runs it), a fault of
``faults.py`` planted under the timed path, or ``control``: the plain
reference put in the program's place and computed one precision step below
what the configuration states (float8_e4m3 matmul operands for bfloat16),
compared with the float32 reference by the same numbers.  Sound runs set
the lower reading of each limit; the control and the faults have to come
out not correct, and the least of their readings sets the upper one.
Prints one JSON line per seed.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time


def control(cell, seed: int) -> dict:
    from benchmarks.chip import check, harness

    steps = cell.checked_steps
    ref = harness.reference_readings(cell, seed, steps)
    low = harness.reference_readings(cell, seed, steps, precision="float8")
    checks, correct = check.compare(low, ref, cell.limits)
    return {"seed": seed, "what": "control", "correct": correct,
            "numbers": {k: v["value"] for k, v in checks.items()},
            "leaves": {k: v.get("leaf") for k, v in checks.items()},
            "loss": low["loss"], "loss_ref": ref["loss"],
            "detail": {"prog": low, "ref": ref}}


def program(cell, seed: int, what: str, seconds: float, keep_trace) -> dict:
    from benchmarks.chip import faults, harness

    wrap = None if what == "sound" else faults.FAULTS[what]
    detail = {}
    out = harness.run(cell, seed, seconds, keep_trace is not None,
                      t_start=time.perf_counter(), wrap_step=wrap,
                      keep_trace=keep_trace, detail=detail,
                      log=lambda msg: print(f"bench: {msg}", file=sys.stderr,
                                            flush=True))
    return {"seed": seed, "what": what, "correct": out["correct"],
            "numbers": {k: v["value"] for k, v in out["checks"].items()},
            "leaves": {k: v.get("leaf") for k, v in out["checks"].items()},
            "loss": detail["prog"]["loss"], "loss_ref": detail["ref"]["loss"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "device": out["device"], "detail": detail}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", default="sound")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--keep-trace", default=None)
    ap.add_argument("--out", default=None,
                    help="file to append each seed's full readings to")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from benchmarks.chip import harness, spec

    cell = spec.load_cell(args.workload, root)
    harness.find_chips(cell.chips)
    harness.use_compile_cache()
    for seed in args.seeds:
        t = time.perf_counter()
        if args.what == "control":
            res = control(cell, seed)
        else:
            keep = (None if args.keep_trace is None
                    else os.path.join(args.keep_trace, str(seed)))
            res = program(cell, seed, args.what, args.seconds, keep)
        res["seconds"] = time.perf_counter() - t
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(res) + "\n")
        res.pop("detail")
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
