"""Readings that set a cell's correctness limit: the program's widest
logit gap and the control's, over several seeds in one process.

    python -m chipbench.calibrate --workload starcoder2-7b-int4.complete \
        --seeds 11,12,13 --seconds 40 [--out FILE]

Each seed is a whole run of the cell at its own size and load (build,
warm-up, window) whose served tokens are read against the reference
(``logit_gap``), and whose prompts and tokens are read once more with the
reference at int4 activations in the program's place
(``control_<name>_logit_gap``), and judged by the same predicate that
decides the program's ``correct`` (``controls``: each must read false).
The limit in the configuration's ``check`` goes above the largest
program reading and below the smallest control reading.  The
benchmark's own runs never run the control.  Prints one JSON line per
seed.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="int4")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from .bench import load_cell
    from .harness import NoDevice, log, run_cell

    cell = load_cell(args.workload)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = T0 if i == 0 else time.perf_counter()
        try:
            res = run_cell(cell, seed, args.seconds, False, t0,
                           controls=tuple(args.controls.split(",")))
        except NoDevice as e:
            log(f"FAIL: {e}")
            return 3
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "correct": res["correct"],
                           "controls": res.get("controls", {}),
                           "metrics": res["metrics"],
                           "device": res["device"],
                           "checks": res["checks"]})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
