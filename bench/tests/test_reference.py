"""The plain references agree with the program at small sizes.

The references import nothing of the program; these tests are where the
two meet, so that a reference that drifted from the program's semantics
shows here and not first on the chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import dag as rdag
from bench.reference import frontier as ref


def _rows(rng, F, K, family):
    W = rng.dirichlet(np.ones(K), F)
    W[0, -1] = 0.0                       # a channel with no work
    W[0] /= W[0].sum()
    mus = rng.uniform(1.0, 4.0, (F, K))
    sgs = mus * rng.uniform(0.05, 0.4, (F, K))
    rho = np.full((F, K), 0.35 if family == "drift" else 0.0)
    return W, mus, sgs, rho


@pytest.fixture
def x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


@pytest.mark.parametrize("family", ["normal", "lognormal", "drift"])
def test_moments_match_the_program(family, x64):
    from repro.core.distributions import Drift
    from repro.kernels import ops

    rng = np.random.default_rng(3)
    F, K, T = 8, 5, 64
    W, mus, sgs, rho = _rows(rng, F, K, family)
    mask = np.ones((F, K), bool)
    r_mu, r_var = ref.stage_moments(
        *ref.as_dtype([W, mus, sgs, rho, mask], jnp.float64),
        family=family, num_t=T)
    fam = Drift(0.35) if family == "drift" else family
    p_mu, p_var = ops.frontier_moments(
        W.astype(np.float32), mus.astype(np.float32),
        sgs.astype(np.float32), num_t=T, impl="xla", family=fam)
    r_mu = np.asarray(r_mu)
    assert np.max(np.abs(np.asarray(p_mu) - r_mu) / r_mu) < 1e-5
    assert np.max(np.abs(np.asarray(p_var) - np.asarray(r_var))
                  / r_mu ** 2) < 1e-5


def test_clark_composition_matches_the_program(x64):
    from repro.workflow.dag import Stage, StageDAG

    rng = np.random.default_rng(5)
    names = ["src", "a0", "a1", "b0", "c0", "sink"]
    edges = [("src", "a0"), ("a0", "a1"), ("src", "b0"), ("src", "c0"),
             ("a1", "sink"), ("b0", "sink"), ("c0", "sink")]
    sm = rng.uniform(5.0, 9.0, len(names))
    sv = rng.uniform(0.2, 2.0, len(names))
    dag = StageDAG([Stage(n, [1.0], [0.1]) for n in names], edges)
    p_m, p_v = dag.compose_moments(jnp.asarray(sm, jnp.float32),
                                   jnp.asarray(sv, jnp.float32))
    m, v = rdag.makespan(names, edges, sm, sv)
    assert abs(float(p_m) - m) / m < 1e-6
    assert abs(float(p_v) - v) / m ** 2 < 1e-6


def test_monte_carlo_agrees_where_clark_is_exact(x64):
    # one channel per stage and two independent branches: every operand of
    # the join is exactly normal and independent, where Clark's moments are
    # exact, so only the sampling error separates the two
    names = ["a", "b", "sink"]
    edges = [("a", "sink"), ("b", "sink")]
    rng = np.random.default_rng(9)
    stats = {"a": ("normal", np.array([10.0]), np.array([2.0]), 0.0),
             "b": ("normal", np.array([11.0]), np.array([1.5]), 0.0),
             "sink": ("normal", np.array([4.0]), np.array([0.5]), 0.0)}
    w = {n: np.array([1.0]) for n in names}
    mc = rdag.mc_makespan(names, edges, stats, w, 200000, rng)
    sm = np.array([stats[n][1][0] for n in names])
    sv = np.array([stats[n][2][0] ** 2 for n in names])
    m, _ = rdag.makespan(names, edges, sm, sv)
    assert abs(mc - m) / m < 2e-3
