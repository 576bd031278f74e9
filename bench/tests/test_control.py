"""The control, the reference in bfloat16 in the program's place, comes out
not correct on every cell's numbers, while the program comes out correct."""
import jax  # noqa: F401  (imported before the harness sets its cache path)
import pytest

from bench import control
from bench.tests import small


@pytest.mark.parametrize("workload", ["epigenomics.cold"])
def test_control_is_not_correct(workload, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    r = control.readings(workload, 2**31 + 5, 2.0,
                         require_tpu=False,
                         overrides=small.overrides(workload))
    assert r["correct"], r
    assert not r["control_correct"], r
    over = [k for k, v in r["control"].items() if v > r["limit"][k]]
    assert over, r
