"""The roofline reader gives each kernel event its shape by its place in the
solver's ladder."""
import pytest

from bench import run as bench_run
from bench import trace_reduce as tr
from bench.kernels import frontier_grid as kfg
from bench.peaks import PEAKS

KIND = "TPU v5 lite"
PEAK = PEAKS[KIND]
LADDER = {"presolve": 128, "refine": 256, "final": 2048}

# a launch of 512 rows of 6 channels as the chip's profiler names it
HLO = ("%frontier_grid_grad_lognormal.1 = (f32[1,1,512]{2,1,0:T(1,128)}, "
       "f32[1,1,512]{2,1,0:T(1,128)}, f32[1,6,512]{2,1,0:T(8,128)}, "
       "f32[1,6,512]{2,1,0:T(8,128)}) custom-call(f32[1,6,512]{2,1,0:T(8,128)"
       "S(1)} %bitcast.25, f32[1,6,512]{2,1,0:T(8,128)} %bitcast.26, "
       "f32[1,6,512]{2,1,0:T(8,128)} %bitcast.27, f32[1,1,6,512]{3,2,1,0:"
       "T(8,128)S(1)} %copy_bitcast_fusion), custom_call_target="
       "\"tpu_custom_call\"")


def _least(mode, F, K, T, family):
    return max(kfg.ops(F, K, T, mode) / PEAK["flops_per_s"],
               kfg.bytes_moved(F, K, mode, family) / PEAK["hbm_bytes_per_s"])


def _reader():
    reader, suffix = bench_run.metric_reader("frontier_grid_roofline.solve")
    assert suffix == "solve"
    return reader


def test_kernel_shape_from_the_hlo_text():
    assert tr.kernel_shape(HLO) == (512, 6)
    two_blocks = HLO.replace("f32[1,", "f32[2,")
    assert tr.kernel_shape(two_blocks) == (1024, 6)
    assert tr.kernel_shape("%fusion.3 = f32[8]{0} fusion()") is None


def test_shapes_from_the_ladder_skip_a_solve_off_it():
    g, f = "frontier_grid_grad_normal", "frontier_grid_fwd_normal"
    trace = {"annotations": [("bench.solve", 1000, 9000),
                             ("bench.solve", 10000, 19000)],
             "kernel_events": [
                 (g, 1100, 1300, 96, 256), (g, 1400, 1600, 96, 256),
                 (f, 1700, 1800, 192, 256),
                 (g, 2000, 2100, 32, 256),
                 (f, 5000, 6000, 96, 256),
                 # the second solve has one forward launch: not the ladder
                 (g, 11000, 12000, 96, 256), (f, 13000, 14000, 96, 256)]}
    rec = {"trace": trace, "device_kind": KIND, "ladder_t": LADDER}
    least = (2 * _least("grad", 96, 256, 128, "normal")
             + _least("fwd", 192, 256, 128, "normal")
             + _least("grad", 32, 256, 256, "normal")
             + _least("fwd", 96, 256, 2048, "normal"))
    took = (200 + 200 + 100 + 100 + 1000) * 1e-9
    assert _reader().read(rec, "solve") == pytest.approx(100 * least / took)


def test_nothing_to_read_is_none():
    assert _reader().read({"device_kind": KIND}, "solve") is None
    rec = {"trace": {"kernel_events": [], "annotations": []},
           "device_kind": KIND, "ladder_t": LADDER}
    assert _reader().read(rec, "solve") is None


def test_an_unknown_device_is_an_error():
    f = "frontier_grid_fwd_normal"
    rec = {"trace": {"annotations": [("bench.solve", 0, 100)],
                     "kernel_events": [(f, 1, 2, 8, 6), (f, 3, 4, 8, 6)]},
           "device_kind": "TPU v9 imaginary", "ladder_t": LADDER}
    with pytest.raises(KeyError):
        _reader().read(rec, "solve")
