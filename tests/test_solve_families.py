"""The solvers' decision invariants in every completion-time family.

``solve_dag`` over three DAG shapes and two risk weights in each family:
every returned row lies on its stage's simplex, a single-channel stage's
split is exactly [1.0], the shared evaluator reproduces the reported
makespan moments from the returned split, and the returned objective is no
worse than that of the two baselines the solver starts from (the equal and
the inverse-mean split). The stage-by-stage baseline ``solve_dag_greedy``
in the same cases: its rows lie on their simplices, the evaluator
reproduces its moments, and the joint solve is no worse on the composed
mean.

This file runs the families without per-channel shape parameters;
``test_solve_families_params`` (drift, defective) and
``test_solve_families_empirical`` run the others through the same checks.
"""
import functools

import numpy as np
import pytest

from repro.workflow import evaluate_dag, solve_dag, solve_dag_greedy
from workflow_shapes import make_dag

SHAPES = ("lane", "diamond", "wide")
EVAL_T = 128
F32_EPS = float(np.finfo(np.float32).eps)


@functools.lru_cache(maxsize=None)
def joint(shape, family, lam_var):
    """One cheap joint solve per case (also the greedy check's yardstick)."""
    dag = make_dag(shape, family, seed=SHAPES.index(shape))
    return dag, solve_dag(dag, lam_var=lam_var, steps=10, restarts=1,
                          num_t=64, presolve_num_t=32, eval_num_t=EVAL_T)


def objective(dec, lam_var):
    return dec.makespan_mu + lam_var * dec.makespan_var


def assert_on_simplex(dag, weights):
    for s in dag.stages:
        w = weights[s.name]
        assert w.shape == (s.k,)
        assert (w >= 0).all() and abs(float(w.sum()) - 1.0) <= 1e-6, s.name


def assert_evaluator_agrees(dag, dec):
    """The evaluator, one row in its own launch, gives the moments the
    solver reported for the same split from its candidate pool. The
    variance is a difference of second-moment terms of size mu**2, so in
    float32 it also carries an absolute error of a few ulps of mu**2."""
    ev = evaluate_dag(dag, dec.weights, num_t=EVAL_T)
    np.testing.assert_allclose(ev.makespan_mu, dec.makespan_mu, rtol=1e-5)
    np.testing.assert_allclose(ev.makespan_var, dec.makespan_var, rtol=1e-5,
                               atol=8 * F32_EPS * dec.makespan_mu ** 2)


def check_joint(family, shape, lam_var):
    dag, dec = joint(shape, family, lam_var)
    assert_on_simplex(dag, dec.weights)
    for s in dag.stages:
        if s.k == 1:
            assert dec.weights[s.name].tolist() == [1.0], s.name
    assert_evaluator_agrees(dag, dec)
    equal = {s.name: np.full(s.k, 1.0 / s.k) for s in dag.stages}
    inv_mu = {s.name: (1.0 / s.mus) / np.sum(1.0 / s.mus)
              for s in dag.stages}
    got = objective(dec, lam_var)
    for base in (equal, inv_mu):
        want = objective(evaluate_dag(dag, base, num_t=EVAL_T), lam_var)
        assert got <= want * (1 + 1e-6), (got, want)


def check_greedy(family, shape):
    dag, dec = joint(shape, family, 0.0)
    greedy = solve_dag_greedy(dag, steps=10, restarts=1, num_t=64,
                              eval_num_t=EVAL_T)
    assert_on_simplex(dag, greedy.weights)
    assert_evaluator_agrees(dag, greedy)
    # a chain is where stage-by-stage is already optimal: a tie, to the
    # few steps both solvers take
    assert dec.makespan_mu <= greedy.makespan_mu * (1 + 1e-3), (
        dec.makespan_mu, greedy.makespan_mu)


FAMILIES_HERE = ("normal", "lognormal")


@pytest.mark.parametrize("lam_var", [0.0, 0.5])
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("family", FAMILIES_HERE)
def test_joint_decision_invariants(family, shape, lam_var):
    check_joint(family, shape, lam_var)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("family", FAMILIES_HERE)
def test_greedy_decision_invariants(family, shape):
    check_greedy(family, shape)
