"""The frontier_grid operation and byte counts, against shapes worked by hand."""
import pytest

from bench.kernels import frontier_grid as kfg


@pytest.mark.parametrize("F,K,T,mode,expect", [
    # fwd: F*T*(34*K + 6)
    (1, 1, 1, "fwd", 40.0),
    (8, 6, 128, "fwd", 8 * 128 * (34 * 6 + 6)),
    (4096, 1024, 256, "fwd", 4096 * 256 * (34 * 1024 + 6)),
    # grad adds 18 per point and channel, pgrad 22
    (8, 6, 128, "grad", 8 * 128 * (52 * 6 + 6)),
    (96, 256, 2048, "grad", 96 * 2048 * (52 * 256 + 6)),
    (2, 3, 5, "pgrad", 2 * 5 * (56 * 3 + 6)),
])
def test_ops(F, K, T, mode, expect):
    assert kfg.ops(F, K, T, mode) == expect


@pytest.mark.parametrize("F,K,mode,family,expect", [
    # in: W, mus, sigmas and one row of family parameters per (row, channel)
    (8, 6, "fwd", "normal", 4 * (8 * 6 * 4 + 2 * 8)),
    (8, 6, "grad", "drift", 4 * (8 * 6 * 4 + 2 * 8 + 2 * 8 * 6)),
    (8, 6, "fwd", "defective", 4 * (8 * 6 * 5 + 2 * 8)),
])
def test_bytes(F, K, mode, family, expect):
    assert kfg.bytes_moved(F, K, mode, family) == expect


@pytest.mark.parametrize("name,expect", [
    ("frontier_grid_fwd_normal", ("fwd", "normal")),
    ("frontier_grid_grad_lognormal", ("grad", "lognormal")),
    ("frontier_grid_pgrad_drift", ("pgrad", "drift")),
    ("fusion.12", None),
    ("frontier_grid_other_normal", None),
])
def test_parse_name(name, expect):
    assert kfg.parse_name(name) == expect
