"""Run one cell of ``BENCHMARK.json`` once, on the chip this process holds.

    python3 -m bench.run --workload epigenomics.cold --seed 7 --seconds 51 --trace 0

The cell names a configuration (``bench/configs/<config>.json``, whose
``system`` key names ``bench/systems/<system>.py``) and a traffic mix
(``bench/traffic/<mix>.json``). A run:

1. stops with exit code 2, printing no result, unless JAX's first device is
   a TPU and there are as many devices as the cell asks for;
2. keeps JAX's persistent compilation cache in ``<checkout>/.jax_cache``;
3. lets the system's module build it from the seed and warm every shape the
   cell's traffic uses (set-up, reported as ``setup_s``);
4. measures for ``--seconds``: the window closes at the first request
   that completes after that. With ``--trace 1`` the program's spans are
   on, the profiler traces a few seconds of the steady window, and each
   request is wrapped in a ``TraceAnnotation``;
5. reads the peak device memory, frees the system, and checks what the
   window produced against the plain reference (``bench/reference``);
6. prints each compared number beside its limit as the last lines of
   standard error, and one JSON object as the last line of standard output:
   ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
   metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
   ``device``, with ``--trace 1`` ``breakdown``, and last ``check``.

Every metric is computed by ``bench/metrics/<name>.py`` (or, for a name
with a suffix such as ``.p95``, ``<name without the suffix>.py``) from the
run's record; a reader that finds nothing returns None and the metric is
left out of the line.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
from contextlib import nullcontext
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

PROFILE_AT = 1.0 / 3.0    # share of the window before the profiler starts
PROFILE_S = 4.0           # longest profiled stretch, seconds


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_spec(bench: dict, workload: str) -> dict:
    """The cell, its configuration entry and the metric entries it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    return {"cell": cell, "config": config,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def metric_reader(name: str):
    """``bench/metrics/<name>.py``, else the file named without the last
    dotted part, which then receives that part as its ``suffix``."""
    mdir = os.path.join(HERE, "metrics")
    path = os.path.join(mdir, f"{name}.py")
    if os.path.exists(path):
        return load_module(path, f"bench_metric_{name}"), None
    base, _, suffix = name.rpartition(".")
    path = os.path.join(mdir, f"{base}.py")
    if base and os.path.exists(path):
        return load_module(path, f"bench_metric_{base}"), suffix
    raise FileNotFoundError(f"no reader for metric {name!r} in {mdir}")


def read_metrics(entries, record) -> dict:
    out = {}
    for m in entries:
        reader, suffix = metric_reader(m["name"])
        value = reader.read(record, suffix)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class Window:
    """The measured window and what the harness watches inside it.

    A system's module calls :meth:`open` when set-up is done and
    :meth:`step` after each request (it says whether the window has
    closed), and wraps each request in :meth:`annotate`. Compiles are counted by JAX's
    monitoring events: a backend compile, or a program read back from the
    persistent cache, inside the window is a compile the set-up missed.
    """

    def __init__(self, t_start: float, seconds: float, trace: bool,
                 trace_dir: Optional[str]):
        self.t_start = t_start
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.trace_dir = trace_dir
        self.t0 = self.t1 = None
        self.compile_times = []
        self.profile = None          # {"start", "stop", "sync_perf_ns"}
        self.spans = []
        self._obs_mark = 0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_times.append(time.perf_counter())

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.compile_times.append(time.perf_counter())

    @property
    def setup_s(self) -> float:
        return self.t0 - self.t_start

    def open(self) -> float:
        if self.trace:
            from repro.obs import trace as obs
            obs.set_enabled(True)
            self._obs_mark = obs.mark()
        self.t0 = time.perf_counter()
        self.cpu0 = time.process_time()
        return self.t0

    def annotate(self, name: str):
        if not self.trace:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _drain_spans(self) -> None:
        from repro.obs import trace as obs
        recs = obs.records(since=self._obs_mark)
        if recs:
            self._obs_mark = recs[-1]["seq"]
            self.spans.extend(recs)

    def step(self) -> bool:
        """Bookkeeping after one request; True once the window closed."""
        now = time.perf_counter()
        el = now - self.t0
        if self.trace:
            self._drain_spans()
            self._profile_step(el)
        if el >= self.seconds:
            self.close(now)
            return True
        return False

    def _profile_step(self, el: float) -> None:
        import jax
        start_at = self.seconds * PROFILE_AT
        if self.profile is None and el >= start_at:
            jax.profiler.start_trace(self.trace_dir)
            with jax.profiler.TraceAnnotation("bench.sync"):
                sync = time.perf_counter_ns()
            self.profile = {"start": time.perf_counter(), "stop": None,
                            "sync_perf_ns": sync}
        elif (self.profile is not None and self.profile["stop"] is None
              and el >= start_at + min(PROFILE_S, self.seconds / 3.0)):
            self._stop_profile()

    def _stop_profile(self) -> None:
        import jax
        self.profile["stop"] = time.perf_counter()
        jax.profiler.stop_trace()

    def close(self, now: Optional[float] = None) -> None:
        self.t1 = time.perf_counter() if now is None else now
        self.cpu_s = time.process_time() - self.cpu0
        if self.trace:
            if self.profile is not None and self.profile["stop"] is None:
                self._stop_profile()
            from repro.obs import trace as obs
            self._drain_spans()
            obs.set_enabled(False)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def compiles(self) -> int:
        return sum(self.t0 <= t <= self.t1 for t in self.compile_times)


def device_info(devices) -> dict:
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> Optional[int]:
    """Peak bytes in use on the fullest device (None where the backend
    keeps no memory statistics, as the CPU's)."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
             for d in devices]
    return max(peaks) if max(peaks) >= 0 else None


def format_check(check: list) -> dict:
    return {c["name"]: {"value": c["value"], "limit": c["limit"]}
            for c in check}


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, overrides: Optional[dict] = None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``require_tpu=False`` and ``overrides`` (keys merged into the
    configuration and the traffic mix) are for the CPU tests, which drive
    the whole run at a small size.
    """
    return run_record(workload, seed, seconds, trace, require_tpu,
                      overrides)[0]


def run_record(workload: str, seed: int, seconds: float, trace: bool,
               require_tpu: bool = True, overrides: Optional[dict] = None):
    """:func:`run`, returning the run's record beside the result line."""
    t_start = time.perf_counter()
    spec = cell_spec(load_benchmark(), workload)
    # the cache lives in the checkout, whatever the machine sets: the two
    # sides of a comparison must not share one
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    devices = jax.devices()
    chips = int(spec["cell"]["chips"])
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX reports "
                     f"{len(devices)} device(s) of platform "
                     f"{devices[0].platform!r}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    with open(os.path.join(ROOT, spec["config"]["file"])) as f:
        config = json.load(f)
    from bench import traffic
    mix = traffic.load_mix(spec["cell"]["traffic"])
    for key, val in (overrides or {}).items():
        (mix if key in mix else config)[key] = val
    system = load_module(os.path.join(HERE, "systems",
                                      f"{config['system']}.py"),
                         f"bench_system_{config['system']}")

    import tempfile
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        window = Window(t_start, seconds, trace, tdir)
        record = system.run(config, mix, seed, window)
        record["config"], record["system"] = config, system
        record["memory_peak_bytes"] = memory_peak(devices)
        record["device_kind"] = devices[0].device_kind
        record["window_s"] = window.window_s
        record["setup_s"] = window.setup_s
        record["compiles"] = window.compiles()
        record.setdefault("notes", []).append(
            f"harness: set-up {window.setup_s:.3f} s, window "
            f"{window.window_s:.3f} s ({window.cpu_s:.3f} s of this "
            f"process's CPU time), {record['compiles']} compiles in it")
        record["spans"] = window.spans
        if trace:
            from bench import trace_reduce
            record["trace"] = trace_reduce.reduce_dir(
                tdir, window.profile, window.spans)
        check = system.check(record, config, seed)

    entries = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = read_metrics(entries, record)
    device = device_info(devices)
    device["memory_peak_bytes"] = record["memory_peak_bytes"]
    out = {"correct": bool(record["failed"] == 0
                           and all(c["value"] <= c["limit"] for c in check)),
           "attempted": record["attempted"], "failed": record["failed"],
           "metrics": metrics, "device": device}
    if trace:
        tr = record["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    for line in record.get("notes", []):
        print(line, file=sys.stderr)
    out["check"] = format_check(check)
    for c in check:
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {c['name']} {c['value']!r} limit {c['limit']!r} "
              f"{verdict}", file=sys.stderr)
    return out, record


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
