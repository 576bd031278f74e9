"""``profile["launches"]`` against the launches ``solve_dag`` really makes,
in the solver's modes that change which launches a solve makes: an
incremental ``dirty=`` re-solve, a ``done=`` re-solve of sunk work, and the
fragility launch that ``posteriors`` add, on XLA and the Pallas kernel
(the recording is described in ``launch_records``)."""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from launch_records import check, launches, solve  # noqa: F401
from workflow_shapes import make_dag


def _warm(dag):
    return {s.name: np.full(s.k, 1.0 / s.k, np.float32) for s in dag.stages}


@functools.lru_cache(maxsize=None)
def _posteriors(shape):
    """Per-stage NIG posteriors after three observations (built once)."""
    from repro.core.bayes import nig_init, nig_update_batch

    dag = make_dag(shape, "normal")
    rng = np.random.default_rng(0)
    out = {}
    for s in dag.stages:
        nig = nig_init(s.k, m0=float(np.mean(s.mus)))
        for _ in range(3):
            rates = rng.normal(s.mus, s.sigmas).astype(np.float32)
            nig = nig_update_batch(nig, jnp.asarray(rates),
                                   jnp.ones(s.k, jnp.float32))
        out[s.name] = nig
    return out


IMPLS = ("xla", "pallas_interpret")


@pytest.mark.parametrize("impl", IMPLS)
def test_dirty_resolve_books_its_launches(impl, launches):
    dag = make_dag("lane", "normal")
    # the warm split itself is never traced here: the records start empty
    dec = solve(dag, impl, warm_start=_warm(dag), dirty=["map"])
    assert dec.method == "pgd-dag-joint-inc"
    check(dec, launches)


@pytest.mark.parametrize("impl", IMPLS)
def test_done_resolve_books_its_launches(impl, launches):
    dag = make_dag("diamond", "normal")
    ks = {s.name: s.k for s in dag.stages}
    done = {"a": np.full(ks["a"], 1.0 / ks["a"]),
            "b": np.r_[0.3, 0.1, np.zeros(ks["b"] - 2)]}
    dec = solve(dag, impl, done=done)
    p = check(dec, launches)
    assert dec.stage_mu[0] < 1e-6      # all of stage a's work is done
    assert p["fixed_stages"] == 0


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("risk_lam", [0.0, 0.5])
def test_posteriors_book_the_fragility_launch(impl, risk_lam, launches):
    dag = make_dag("lane", "normal")
    dec = solve(dag, impl, posteriors=_posteriors("lane"), risk_lam=risk_lam)
    p = check(dec, launches)
    frag = [e for e in p["launches"] if e["phase"] == "fragility"]
    # the whole pool is scored risk-adjusted, else the winner alone
    want = p["pool"] if risk_lam > 0 else 1
    assert [e["mode"] for e in frag] == ["pgrad"]
    assert frag[0]["rows"] == want * len(dag.stages)
    assert dec.fragility is not None and dec.fragility > 0
