"""Readings that set the limits of a cell's comparison with its reference.

    python3 -m bench.control --workload epigenomics.cold --seeds 1,2,3 --seconds 10

For each seed, in this one process: one run of the cell as the benchmark
makes it (the program's numbers, the lower readings), then the control on
the same inputs: the plain reference computed in bfloat16, the precision
below the configuration's float32, put in the program's place (the upper
readings). One JSON line per seed, then one with the largest program
reading and the smallest control reading of each number. The benchmark's
own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench import run as bench_run

CONTROL_DTYPE = "bfloat16"


def readings(workload: str, seed: int, seconds: float,
             require_tpu: bool = True, overrides=None) -> dict:
    out, rec = bench_run.run_record(workload, seed, seconds, False,
                                    require_tpu, overrides)
    control, _ = rec["system"].compare(rec, rec["config"], seed,
                                       dtype_name=CONTROL_DTYPE)
    return {"seed": seed, "correct": out["correct"],
            "program": {k: v["value"] for k, v in out["check"].items()},
            "limit": {k: v["limit"] for k, v in out["check"].items()},
            "control": control,
            "control_correct": all(control[k] <= out["check"][k]["limit"]
                                   for k in control)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            r = readings(args.workload, seed, args.seconds)
        except bench_run.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        rows.append(r)
        print(json.dumps(r), flush=True)
    names = rows[0]["program"]
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "program_max": {k: max(r["program"][k] for r in rows) for k in names},
        "control_min": {k: min(r["control"][k] for r in rows)
                        for k in rows[0]["control"]},
        "program_correct_all": all(r["correct"] for r in rows),
        "control_correct_any": any(r["control_correct"] for r in rows)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
