#!/usr/bin/env python
"""Trace viewer prep: JSONL trace -> Chrome/Perfetto + per-kind summaries.

Usage (from the repo root)::

    python scripts/trace_view.py TRACE_serve_trace_smoke.jsonl
    python scripts/trace_view.py trace.jsonl -o trace.perfetto.json

Reads a ``repro.obs`` JSONL trace (one record per line, as written by
``repro.obs.export.write_jsonl`` / the serve CLI's ``--trace``), validates
every record against the event schema, writes the Chrome trace_event file
Perfetto and chrome://tracing load directly, and prints a per-name summary
table (count, total/mean duration for spans; count per audit event type).
"""
import argparse
import os
import sys
from collections import defaultdict

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))

from repro.obs import export as obs_export  # noqa: E402


def summarize(records) -> str:
    """Per-name table: spans get count/total/mean µs, events get counts."""
    spans = defaultdict(list)
    events = defaultdict(int)
    for r in records:
        if r["type"] == "span":
            spans[r["name"]].append(float(r["dur_us"]))
        else:
            events[r["name"]] += 1
    lines = [f"{'name':<24}{'count':>8}{'total_us':>14}{'mean_us':>12}"]
    for name in sorted(spans):
        ds = spans[name]
        lines.append(f"{name:<24}{len(ds):>8}{sum(ds):>14.1f}"
                     f"{sum(ds) / len(ds):>12.1f}")
    for name in sorted(events):
        lines.append(f"{name:<24}{events[name]:>8}{'-':>14}{'-':>12}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("jsonl", help="JSONL trace file (repro.obs records)")
    ap.add_argument("-o", "--out", default=None,
                    help="Perfetto output path (default: "
                         "<input stem>.perfetto.json)")
    args = ap.parse_args(argv)

    records = obs_export.read_jsonl(args.jsonl)
    n = obs_export.validate_records(records)
    out = args.out or (os.path.splitext(args.jsonl)[0] + ".perfetto.json")
    obs_export.write_perfetto(records, out)

    kinds = obs_export.span_kinds(records)
    types = obs_export.event_types(records)
    print(f"{args.jsonl}: {n} records, {len(kinds)} span kinds, "
          f"{len(types)} audit event types -> {out}")
    print(summarize(records))
    return 0


if __name__ == "__main__":
    sys.exit(main())
