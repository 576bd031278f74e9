"""From a profiler trace (``.xplane.pb``) to the device's busy time.

Device planes are those named ``/device:<platform>:<n>`` other than the
host's; their op-level line (``XLA Ops``, else every line) gives the
intervals in which an operation ran. Busy time is the union of those
intervals inside the traced window, per device, averaged over devices;
the idle share is one less busy over the window.

The window is the stretch the harness traced. Its ends are host times
(``time.perf_counter``); a ``bench.sync`` annotation recorded at the start
of the trace ties that clock to the profiler's, and the same offset places
the program's own spans (``repro.obs``, stamped with ``perf_counter_ns``)
on the device's timeline, so each idle gap is named by the innermost span
the host was in.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[int, int]

TOP = 10

# an op event of the TPU trace is named by its HLO instruction,
# ``%frontier_grid_grad_normal.7 = (f32[...]) custom-call(...)``; a module
# event by its program, ``jit_nig_update_batch(2723745867915686307)``
_HLO_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")
_MODULE_NAME = re.compile(r"^([^(]+)\(\d+\)$")


def op_name(name: str) -> str:
    """The operation's name without its HLO text or instance number."""
    for pat in (_HLO_NAME, _MODULE_NAME):
        m = pat.match(name)
        if m:
            return m.group(1)
    return name


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    """Idle stretches of ``[lo, hi]`` between merged busy intervals."""
    out, cur = [], lo
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


def device_lines(pd, line: str = "XLA Ops") -> Dict[str, list]:
    """Events of one line of each device plane, by operation name:
    ``{plane name: [(name, start, end)]}``."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        lines = [ln for ln in plane.lines if ln.name == line]
        evs = [(op_name(ev.name), int(ev.start_ns), int(ev.end_ns))
               for ln in lines for ev in ln.events]
        if evs:
            out[plane.name] = evs
    return out


_SHAPE = re.compile(r"f32\[(\d+),(\d+),(\d+)\]")


def kernel_shape(hlo: str):
    """(rows, channels) of a ``frontier_grid`` launch from its HLO text:
    the first output is (blocks, 1, block rows), the first operand
    (blocks, channels, block rows). None where the text does not say."""
    out = _SHAPE.search(hlo)
    arg = _SHAPE.search(hlo, hlo.find("custom-call("))
    if not out or not arg or "custom-call(" not in hlo:
        return None
    return int(out.group(1)) * int(out.group(3)), int(arg.group(2))


def kernel_events(pd, lo: int, hi: int, prefix: str = "frontier_grid"):
    """``(name, start, end, rows, channels)`` of the kernel's launches
    inside ``[lo, hi]``, in time order."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        for ln in plane.lines:
            if ln.name != "XLA Ops":
                continue
            for ev in ln.events:
                name = op_name(ev.name)
                a, b = int(ev.start_ns), int(ev.end_ns)
                if name.startswith(prefix) and lo <= a and b <= hi:
                    shape = kernel_shape(ev.name) or (None, None)
                    out.append((name, a, b) + shape)
    return sorted(out, key=lambda e: e[1])


def self_times(evs: list, lo: int, hi: int) -> Dict[str, int]:
    """Time of each operation inside ``[lo, hi]`` less the time of the
    operations nested in it (a loop holds the kernels it runs)."""
    out: Dict[str, int] = {}
    stack: list = []            # [name, end, self time so far]
    for name, a, b in sorted(evs, key=lambda e: (e[1], -e[2])):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        while stack and stack[-1][1] <= a:
            n, _, t = stack.pop()
            out[n] = out.get(n, 0) + t
        if stack:
            stack[-1][2] -= b - a
        stack.append([name, b, b - a])
    for n, _, t in stack:
        out[n] = out.get(n, 0) + t
    return out


def host_events(pd, name: str) -> list:
    return [(int(ev.start_ns), int(ev.end_ns))
            for plane in pd.planes if plane.name.startswith("/host:")
            for ln in plane.lines for ev in ln.events if ev.name == name]


def annotations(pd, prefix: str = "bench.") -> list:
    """The harness's own host annotations: ``(name, start_ns, end_ns)``."""
    return sorted((ev.name, int(ev.start_ns), int(ev.end_ns))
                  for plane in pd.planes if plane.name.startswith("/host:")
                  for ln in plane.lines for ev in ln.events
                  if ev.name.startswith(prefix) and ev.name != "bench.sync")


def reduce_events(devices: Dict[str, list], lo: int, hi: int,
                  spans: Optional[List[Tuple[str, int, int]]] = None) -> dict:
    """The reduction proper, on plain events (testable without a trace).

    ``devices``: per device, ``(op name, start_ns, end_ns)``; ``[lo, hi]``
    the window in the same clock; ``spans`` host activity as
    ``(label, start_ns, end_ns)`` used to name idle gaps. ``op_s`` and
    ``device_ops`` give each operation's own time, without the operations
    nested in it.
    """
    window = hi - lo
    busy_total, per_op, per_gap = 0, {}, []
    for evs in devices.values():
        merged = union(clip([(a, b) for _, a, b in evs], lo, hi))
        busy_total += sum(b - a for a, b in merged)
        for name, t in self_times(evs, lo, hi).items():
            per_op[name] = per_op.get(name, 0) + t
        per_gap.extend(gaps(merged, lo, hi))
    n = max(len(devices), 1)
    busy = busy_total / n
    per_gap.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in per_gap[:TOP]:
        named.append([_label(a, b, spans or []), (b - a) / 1e9])
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy / 1e9,
        "window_s": window / 1e9,
        "idle_share": 1.0 - busy / window if window > 0 else None,
        "op_s": {k: v / n / 1e9 for k, v in per_op.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in ops_sorted[:TOP]],
        "idle_gaps": named,
        "devices": len(devices),
    }


def _label(a: int, b: int, spans) -> str:
    """The innermost host span covering most of the gap ``[a, b]``."""
    best, best_key = "host (no span)", None
    for label, s, e in spans:
        cover = min(b, e) - max(a, s)
        if cover <= 0:
            continue
        key = (-cover, e - s)
        if best_key is None or key < best_key:
            best, best_key = label, key
    return best


def span_label(rec: dict) -> str:
    attrs = rec.get("attrs") or {}
    for k in ("stage", "phase", "mode"):
        if k in attrs:
            return f"{rec['name']}:{attrs[k]}"
    return rec["name"]


def reduce_dir(trace_dir: str, profile: Optional[dict],
               obs_spans: list) -> dict:
    """Reduce the one trace under ``trace_dir`` for the window in
    ``profile`` (``start``/``stop`` in perf_counter seconds and the
    ``sync_perf_ns`` of the ``bench.sync`` annotation)."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if profile is None or not paths:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    sync = host_events(pd, "bench.sync")
    if not sync:
        raise RuntimeError("the trace has no bench.sync annotation")
    offset = sync[0][0] - int(profile["sync_perf_ns"])
    lo = int(profile["start"] * 1e9) + offset
    hi = int(profile["stop"] * 1e9) + offset
    spans = [(span_label(r), int(r["ts_us"] * 1e3) + offset,
              int((r["ts_us"] + r["dur_us"]) * 1e3) + offset)
             for r in obs_spans if r.get("type") == "span"]
    devs = device_lines(pd)
    out = reduce_events(devs, lo, hi, spans)
    out["kernel_events"] = kernel_events(pd, lo, hi)
    out["annotations"] = [(n, a, b) for n, a, b in annotations(pd)
                          if lo <= a and b <= hi]
    out["offset_ns"] = offset
    return out
