"""Benchmark harness — one module per paper table/figure + beyond-paper.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit) and
writes detailed tables under experiments/bench/.

    PYTHONPATH=src python -m benchmarks.run [--only fig1,cluster]
"""
import argparse
import sys
import traceback

from . import (cluster_scale, fig1_theory, fig2_frontier, fig34_convex_opt,
               fig56_file_transfer, partitioned_training)

SUITES = {
    "fig1": fig1_theory,
    "fig2": fig2_frontier,
    "fig34": fig34_convex_opt,
    "fig56": fig56_file_transfer,
    "cluster": cluster_scale,
    "parttrain": partitioned_training,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    chosen = [s.strip() for s in args.only.split(",") if s.strip()] or list(SUITES)
    print("name,us_per_call,derived")
    failures = []
    for name in chosen:
        try:
            SUITES[name].run()
        except Exception as e:  # noqa: BLE001 — report, keep going
            failures.append((name, e))
            traceback.print_exc()
    if failures:
        print(f"FAILED suites: {[n for n, _ in failures]}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
