"""Shared benchmark utilities: timing + CSV emission."""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments", "bench")


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.2f},{derived}")


def timeit(fn, *args, repeats: int = 5, warmup: int = 2) -> float:
    """Median wall-time per call in microseconds (fn must block)."""
    return timeit_stats(fn, *args, repeats=repeats, warmup=warmup)[0]


def timeit_stats(fn, *args, repeats: int = 5, warmup: int = 2):
    """(median_us, p90_us) wall-time per call (fn must block).

    p90 is what the perf-trajectory JSON tracks: scheduler ticks sit on the
    step critical path, so the tail matters as much as the median.
    """
    for _ in range(warmup):
        fn(*args)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(*args)
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    p90 = times[min(len(times) - 1, int(round(0.9 * (len(times) - 1))))]
    return times[len(times) // 2], p90


def save_table(fname: str, header: str, rows) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, fname)
    with open(path, "w") as f:
        f.write(header + "\n")
        for r in rows:
            f.write(",".join(str(x) for x in r) + "\n")
    return path
