"""The reader of the solver's count of stages composed as constants,
``solver.fixed_stages``: the mean of ``profile["fixed_stages"]`` over the
window's decisions."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import run as bench_run
from bench.systems import dag as dag_system
from bench.systems import montage
from bench.tests.test_montage import small_montage

SEED = 2**31 + 97


def _read(record, name="solver.fixed_stages.montage"):
    reader, suffix = bench_run.metric_reader(name)
    assert suffix == name.rpartition(".")[2]
    return reader.read(record, suffix)


def _log(*profiles):
    return [(None, SimpleNamespace(profile=p)) for p in profiles]


@pytest.mark.parametrize("rec, want", [
    ({"kind": "dag", "log": _log({"fixed_stages": 5},
                                 {"fixed_stages": 5})}, 5.0),
    # a no-op re-solve returns before the ladder, with no count
    ({"kind": "dag", "log": _log({"fixed_stages": 17}, {"noop": True})}, 17.0),
    # a program that does not count them
    ({"kind": "dag", "log": _log({"readbacks": 2}, None)}, None),
    ({"kind": "dag", "log": []}, None),
    ({"kind": "dag"}, None),
    ({"kind": "serve", "log": _log({"fixed_stages": 5})}, None)],
    ids=["mean", "noop-skipped", "no-counter", "empty", "no-log", "not-dag"])
def test_fixed_stages_average_the_counted_decisions(rec, want):
    assert _read(rec) == want


def _montage():
    names, edges, mus, sigmas = montage.make_dag(small_montage(), SEED)
    return names, edges, mus, sigmas


def _wide():
    rng = np.random.default_rng(SEED)
    mus = [rng.uniform(10, 40, k) for k in (3, 2, 4)]
    return ["a", "b", "c"], [("a", "b"), ("b", "c")], mus, \
        [0.2 * m for m in mus]


@pytest.mark.parametrize("make, want", [(_montage, 5), (_wide, 0)],
                         ids=["montage", "no-single-channel"])
def test_the_program_feeds_the_reader(make, want):
    """Montage's chain at a small size keeps its five single jobs
    (mConcatFit, mBgModel, the last mImgtbl, mAdd and mJPEG); a chain of
    stages two or more channels wide has none."""
    from repro.workflow.solve import solve_dag

    names, edges, mus, sigmas = make()
    assert sum(len(m) == 1 for m in mus) == want
    dag = dag_system._stage_dag(names, edges, mus, sigmas, "normal")
    dec = solve_dag(dag, steps=4, restarts=1, num_t=16, eval_num_t=16)
    assert _read({"kind": "dag", "log": [(None, dec), (None, dec)]}) == want


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_launch_counters_with_a_fixed_stage(impl):
    """The lane counters of a solve whose DAG holds a single-channel
    stage: that stage's launches (one a PGD phase) are one channel wide,
    every other launch pads to the widest stage; each entry's ``pack`` is
    what ``ops`` launches with and the ``kernel.lane_share`` reader over
    the solve agrees with a count from the entries."""
    from bench.tests.test_lane_share_metric import _dag, _reader
    from repro.kernels import autotune
    from repro.workflow import solve_dag

    dag = _dag([40, 1, 24])
    dec = solve_dag(dag, steps=2, restarts=1, num_t=16, eval_num_t=16,
                    presolve_num_t=16, impl=impl)
    used = lanes = 0
    for e in dec.profile["launches"]:
        assert e["k"] == (1 if e["channels"] == e["rows"] else 40)
        assert e["rows_padded"] % e["block_f"] == 0
        want = (1 if impl == "xla"
                else autotune.pack_factor(e["block_f"], e["k"]))
        assert e["pack"] == want
        programs = e["rows_padded"] // e["block_f"]
        assert e["lanes"] == programs * 128 * -(-e["block_f"] * want // 128)
        used += e["launches"] * e["rows"] * e["pack"]
        lanes += e["launches"] * e["lanes"]
    # 3 starts x 2 stages that can move: the presolve's 6 rows pack 5
    # slots a row (ceil(40 / 5) = 8 channels, one sublane chunk); the
    # single-channel stage's 3 rows, one launch a phase, pack none
    fixed, pre = dec.profile["launches"][:2]
    assert pre["phase"] == "presolve" and pre["rows"] == 6
    assert pre["pack"] == (1 if impl == "xla" else 5)
    assert (fixed["phase"], fixed["rows"], fixed["k"], fixed["launches"],
            fixed["pack"]) == ("presolve", 3, 1, 1, 1)
    assert [(e["phase"], e["k"]) for e in dec.profile["launches"]
            if e["k"] == 1] == [("presolve", 1), ("refine", 1)]
    got = _reader().read({"kind": "dag", "log": [(None, dec)]}, "montage")
    assert got == pytest.approx(100.0 * used / lanes)


def test_pad_share_counts_fixed_stages_at_one_channel():
    """``kernel.pad_share`` over traced solves of the small Epigenomics
    configuration, against a hand count: triage and the final score pad
    each row to the widest stage; a PGD rung's loop holds the stages
    wider than one channel, padded to the widest, and one launch a rung
    the single channels, one slot each; every launch pads its rows to a
    multiple of its block of at most 8."""
    from bench.tests.test_span_metrics import _read as read_solve
    from bench.tests.test_span_metrics import _small_window

    rec, mus, num_t = _small_window()
    ks = np.array([len(m) for m in mus])
    wide, single = ks[ks > 1], ks[ks == 1]
    assert len(single) == 7          # 2 fastQSplit, 3 mapMerge, 2 more
    real = slots = 0
    for _, dec in rec["log"]:
        p = dec.profile
        assert p["fixed_stages"] == len(single)
        surv = p["survivors"]
        pnt = p["presolve_num_t"]
        for n, steps, T, widths in (
                (p["starts"], 1, pnt, single),
                (p["starts"], p["presolve_steps_run"], pnt, wide),
                (2 * p["starts"], 1, pnt, ks),
                (surv, 1, num_t, single),
                (surv, p["refine_steps_run"], num_t, wide),
                (3 * surv, 1, p["eval_num_t"], ks)):
            rows = n * len(widths)
            bf = min(8, rows)
            real += steps * n * widths.sum() * T
            slots += steps * -(-rows // bf) * bf * widths.max() * T
    assert read_solve("kernel.pad_share.solve", rec) == pytest.approx(
        100 * (1 - real / slots), abs=0.1)
