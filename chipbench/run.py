"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python -m chipbench.run --workload danube3-4b-int4.chat --seed 7 \
        --seconds 40 --trace 0

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``) and, last, ``checks``: each number compared with the
reference beside its limit.  The checks are also the last lines of
standard error.  Exits nonzero and prints no result when JAX finds no TPU
or fewer chips than the cell asks for, or when the program's sources are
not in the checkout.
"""

import time

T0 = time.perf_counter()  # set-up is timed from here: before any import

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from .bench import load_cell
    from .harness import NoDevice, log, run_cell

    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace), T0)
    except NoDevice as e:
        log(f"FAIL: {e}")
        return 3
    except ImportError as e:
        log(f"FAIL: the program's sources are not in this checkout ({e})")
        return 4
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
