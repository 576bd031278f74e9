"""Without a TPU the harness exits non-zero and prints no result."""
import jax  # noqa: F401  (imported before the harness sets its cache path)
import pytest

from bench import run as bench_run


@pytest.mark.parametrize("workload", ["epigenomics.cold"])
def test_exits_nonzero_without_a_tpu(workload, capsys, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    assert jax.devices()[0].platform != "tpu"
    rc = bench_run.main(["--workload", workload, "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "TPU" in out.err
