"""The reduction from device events to busy time, idle share and kernel time."""
import pytest

from bench import trace_reduce as tr


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 12)]) == \
        [(0, 4), (5, 7), (10, 12)]


def test_gaps_between_busy_intervals():
    assert tr.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps([(0, 10)], 0, 10) == []


def test_reduce_busy_idle_and_kernel_time():
    # one device, window [100, 200): a copy nested in a kernel (as a kernel
    # is nested in the loop that runs it), one op sticking out of the
    # window on each side
    ev = [("frontier_grid_grad_normal", 90, 110),    # 10 inside
          ("fusion", 110, 120),
          ("frontier_grid_grad_normal", 150, 170),
          ("copy", 160, 165),                        # nested in the kernel
          ("frontier_grid_fwd_normal", 195, 230)]    # 5 inside
    spans = [("engine.tick", 0, 300), ("engine.stage:commit", 120, 150),
             ("engine.stage:launch", 170, 196)]
    out = tr.reduce_events({"/device:TPU:0": ev}, 100, 200, spans)
    busy = 20 + 20 + 5                               # [100,120) [150,170) [195,200)
    assert out["busy_s"] == pytest.approx(busy / 1e9)
    assert out["window_s"] == pytest.approx(100 / 1e9)
    assert out["idle_share"] == pytest.approx(1 - busy / 100)
    # own time: the nested copy's 5 is the copy's, not the kernel's
    assert out["op_s"]["frontier_grid_grad_normal"] == pytest.approx(25e-9)
    assert out["op_s"]["copy"] == pytest.approx(5e-9)
    assert out["op_s"]["frontier_grid_fwd_normal"] == pytest.approx(5e-9)
    assert sum(out["op_s"].values()) == pytest.approx(busy / 1e9)
    # the longest gap [120, 150) is the engine's commit, the next [170, 195)
    # its launch stage
    assert out["idle_gaps"][0] == ["engine.stage:commit", pytest.approx(30e-9)]
    assert out["idle_gaps"][1] == ["engine.stage:launch", pytest.approx(25e-9)]
    assert out["device_ops"][0][0] == "frontier_grid_grad_normal"


def test_operation_names_drop_their_hlo_text():
    assert tr.op_name("%frontier_grid_grad_normal.7 = (f32[1,1,32]) "
                      "custom-call(f32[1,256,32] %x)") == \
        "frontier_grid_grad_normal"
    assert tr.op_name("%copy-start.1 = (f32[3]) copy-start(f32[3] %m)") == \
        "copy-start"
    assert tr.op_name("jit_nig_update_batch(2723745867915686307)") == \
        "jit_nig_update_batch"


def test_busy_is_averaged_over_devices():
    devs = {"/device:TPU:0": [("a", 0, 50)], "/device:TPU:1": [("a", 0, 100)]}
    out = tr.reduce_events(devs, 0, 100)
    assert out["busy_s"] == pytest.approx(75e-9)
    assert out["idle_share"] == pytest.approx(0.25)
    assert out["devices"] == 2


def test_recorded_slice_of_a_chip_trace():
    """A 40 ms slice of a ``serve3.poisson`` profile on one TPU v5 lite:
    the reduction agrees with a brute-force count, nanosecond by nanosecond."""
    import json
    import os

    import numpy as np

    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "serve3_trace_slice.json")
    with open(path) as f:
        fx = json.load(f)
    lo, hi = fx["lo"], fx["hi"]
    ops = [tuple(e) for e in fx["ops"]]
    out = tr.reduce_events({"/device:TPU:0": ops}, lo, hi)
    grid = np.zeros(hi - lo, bool)                  # one cell a nanosecond
    for _, a, b in ops:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            grid[a - lo:b - lo] = True
    assert out["busy_s"] == pytest.approx(grid.sum() * 1e-9)
    assert out["idle_share"] == pytest.approx(1 - grid.sum() / grid.size,
                                              abs=1e-3)
    kernels = {n: sum(b - a for m, a, b in ops if m == n) / 1e9
               for n, _, _ in ops if n.startswith("frontier_grid")}
    # nothing runs nested in the kernel, so its own time is all of it
    # the tick's three launches, one per family, as the profiler timed them
    assert kernels == {"frontier_grid_grad_lognormal": pytest.approx(14623e-9),
                       "frontier_grid_grad_normal": pytest.approx(5825e-9),
                       "frontier_grid_grad_drift": pytest.approx(3205e-9)}
    for name, t in kernels.items():
        assert out["op_s"][name] == pytest.approx(t)
    assert out["window_s"] == pytest.approx(0.04)
