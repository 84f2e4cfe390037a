"""Benchmark harness: one module per paper table/figure + kernel and
roofline reports.  Prints ``name,us_per_call,derived`` CSV."""

from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only", default=None,
        help="comma-separated subset: "
             "table1,table2,table3,fig9,kernel,roofline,serving,tuning,"
             "traffic",
    )
    args = ap.parse_args()
    from . import (
        fig9_density,
        kernel_bench,
        roofline,
        serving_bench,
        table1_packing,
        table2_per_result,
        table3_addpack,
        traffic_bench,
        tuning_bench,
    )

    print("name,us_per_call,derived")
    mods = {
        "table1": table1_packing.run,
        "table2": table2_per_result.run,
        "table3": table3_addpack.run,
        "fig9": fig9_density.run,
        "kernel": kernel_bench.run,
        "roofline": roofline.run,
        "serving": serving_bench.run,
        "tuning": tuning_bench.run,
        "traffic": traffic_bench.run,
    }
    if args.only:
        selected = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = sorted(set(selected) - set(mods))
        if unknown:
            # a typo'd --only must fail loudly, not skip benchmarks: a CI
            # lane that silently produced no BENCH_*.json looks green
            ap.error(
                f"unknown benchmark name(s): {', '.join(unknown)} "
                f"(valid: {', '.join(mods)})"
            )
    else:
        selected = list(mods)
    for name in selected:
        mods[name]()


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
