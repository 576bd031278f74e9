"""The Montage configuration builds the workflow its source describes, and a
whole run of its cell on the CPU, at a small size of its own, comes out
correct, while the bfloat16 control and each fault of the DAG cells come
out not correct."""
import jax  # noqa: F401  (imported before the harness sets its cache path)
import numpy as np
import pytest

from bench import control
from bench import run as bench_run
from bench.systems import montage
from bench.tests import small
from bench.tests.test_faults_dag import _dag_fault, fresh  # noqa: F401

SEED = 2**31 + 91
CELL = "montage.cold"


def small_montage() -> dict:
    """Every stage of more than 16 tasks cut to 1% of its tasks and the 16
    tiles to 4, short ladders, on the XLA path. At this size ``descent``
    (over the wide stages) reads 0.977-0.988 on five seeds, 1 for a solve
    that returns its start, so the small run holds it to a limit of its
    own."""
    cfg = small.config("montage")
    wf, chk = cfg["workflow"], cfg["check"]
    chain = [dict(s, tasks=(s["tasks"] // 100 if s["tasks"] > 16
                            else min(s["tasks"], 4)))
             for s in wf["chain"]]
    return {"workflow": dict(wf, chain=chain),
            "solve": dict(cfg["solve"], steps=24, num_t=32, impl="xla"),
            "check": dict(chk, mc_trials=0, solves=3,
                          limits=dict(chk["limits"], descent=0.995)),
            "warm_requests": 1}


def test_the_chain_the_source_describes():
    cfg = small.config("montage")
    wf = cfg["workflow"]
    names, edges, mus, sigmas = montage.make_dag(cfg, SEED)
    ks = [len(m) for m in mus]
    assert len(names) == len(set(names)) == 11
    assert sum(ks) == 10429
    assert edges == list(zip(names, names[1:]))
    per_job = {}
    for stage, k in zip(wf["chain"], ks):
        per_job[stage["job"]] = per_job.get(stage["job"], 0) + k
    assert per_job == wf["jobs"] == {
        "mProjectPP": 2102, "mDiffFit": 6172, "mConcatFit": 1,
        "mBgModel": 1, "mBackground": 2102, "mImgtbl": 17, "mAdd": 17,
        "mShrink": 16, "mJPEG": 1}
    assert ks == [2102, 6172, 1, 1, 2102, 16, 16, 16, 1, 1, 1]
    # an even share gives each task its job's mean runtime times its node's
    # factor; deviations are the configured share of the mean
    for stage, m, s in zip(wf["chain"], mus, sigmas):
        per_task = m / len(m) / wf["mean_s"][stage["job"]]
        assert np.all((per_task >= 0.75) & (per_task <= 1.5))
        assert np.all((s / m >= 0.05) & (s / m <= 0.5))


def test_the_seed_fixes_the_dag():
    cfg = small.config("montage")
    a = montage.make_dag(cfg, SEED)
    b = montage.make_dag(cfg, SEED)
    c = montage.make_dag(cfg, SEED + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a[2], b[2]))
    assert not np.array_equal(a[2][1], c[2][1])


def _run():
    return bench_run.run(CELL, SEED, 2.0, False, require_tpu=False,
                         overrides=small_montage())


def test_small_run_is_correct(fresh):  # noqa: F811
    out = _run()
    assert out["correct"], out["check"]
    assert out["check"]["descent"]["value"] < 0.995


def test_control_is_not_correct(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    r = control.readings(CELL, SEED + 2, 2.0, require_tpu=False,
                         overrides=small_montage())
    assert r["correct"], r
    assert not r["control_correct"], r


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_fault_is_not_correct(kind, fresh):  # noqa: F811
    _dag_fault(kind, fresh)
    out = _run()
    assert not out["correct"], out["check"]
