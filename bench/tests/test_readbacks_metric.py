"""The reader of the solver's read-back count, ``solver.readbacks.solve``:
the mean of ``profile["readbacks"]`` over the window's decisions."""
from types import SimpleNamespace

import pytest

from bench import run as bench_run
from bench.systems import dag as dag_system
from bench.tests import small

SEED = 2**31 + 93


def _read(record):
    reader, suffix = bench_run.metric_reader("solver.readbacks.solve")
    assert suffix == "solve"
    return reader.read(record, suffix)


def _log(*profiles):
    return [(None, SimpleNamespace(profile=p)) for p in profiles]


@pytest.mark.parametrize("rec, want", [
    ({"kind": "dag", "log": _log({"readbacks": 2}, {"readbacks": 3})}, 2.5),
    # a no-op re-solve returns before the ladder, with no count
    ({"kind": "dag", "log": _log({"readbacks": 2}, {"noop": True})}, 2.0),
    # a program that does not count its read-backs
    ({"kind": "dag", "log": _log({"launches": []}, None)}, None),
    ({"kind": "dag", "log": []}, None),
    ({"kind": "dag"}, None),
    ({"kind": "serve", "log": _log({"readbacks": 2})}, None)],
    ids=["mean", "noop-skipped", "no-counter", "empty", "no-log", "not-dag"])
def test_readbacks_average_the_counted_decisions(rec, want):
    assert _read(rec) == want


def test_the_program_feeds_the_reader():
    from repro.workflow.solve import solve_dag

    cfg = small.epigenomics()
    names, edges, mus, sigmas = dag_system.make_dag(cfg, SEED)
    dag = dag_system._stage_dag(names, edges, mus, sigmas, "normal")
    log = [(None, solve_dag(dag, **cfg["solve"], block_f=8))
           for _ in range(2)]
    # one batched transfer at triage, one at the final score
    assert _read({"kind": "dag", "solves": 2, "log": log}) == 2
