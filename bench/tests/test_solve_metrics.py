"""The solve-time readers: ``solve_ms`` over all the window, its median
beside it."""
import pytest

from bench import run as bench_run


def _read(name, record):
    reader, suffix = bench_run.metric_reader(name)
    return reader.read(record, suffix)


def test_a_stall_moves_the_mean_and_not_the_median():
    times = [77.0] * 99 + [2900.0]
    rec = {"kind": "dag", "solves": 100, "window_s": sum(times) / 1e3,
           "solve_times_ms": times}
    assert _read("solve_ms", rec) == pytest.approx(sum(times) / 100)
    assert _read("solve_p50_ms.solve", rec) == 77.0


def test_nothing_to_read_is_none():
    assert _read("solve_ms", {"kind": "dag", "solves": 0}) is None
    assert _read("solve_p50_ms.solve", {"solve_times_ms": []}) is None
