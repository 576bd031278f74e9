"""A DAG run with the timed path broken underneath comes out not correct, once
for each fault the cell can have: a step that returns its state
unchanged; half of the batch left out, the mean taken over the rest; an
answer altered where it is produced. (No cell runs across chips, so none
can leave out an exchange between them.)"""
import jax
import jax.numpy as jnp
import pytest

from bench import run as bench_run
from bench.tests import small

SEED = 2**33 + 17


def _run(workload):
    return bench_run.run(workload, SEED, 2.0, False,
                         require_tpu=False,
                         overrides=small.overrides(workload))


@pytest.fixture
def fresh(monkeypatch, tmp_path):
    """Retrace every jitted function around the fault, so that a patched
    callee is what the traced program calls."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.clear_caches()
    yield monkeypatch
    jax.clear_caches()


# -------------------------------------------------------------------- DAG
def _dag_fault(kind, monkeypatch):
    from repro.kernels import ops
    from repro.workflow import solve

    if kind == "unchanged":
        def still(structure, dist_ids, idxs, stats, masks, W0, *a, **kw):
            R = W0.shape[0]
            return W0, W0, jnp.full((R,), 1e30, jnp.float32), jnp.int32(0)
        monkeypatch.setattr(solve, "_pgd_phase", still)
    elif kind == "half":
        orig = ops.frontier_moments

        def half(W, *a, **kw):
            m, v = orig(W, *a, **kw)
            h = W.shape[0] // 2
            return (m.at[h:].set(jnp.mean(m[:h])),
                    v.at[h:].set(jnp.mean(v[:h])))
        monkeypatch.setattr(ops, "frontier_moments", half)
    elif kind == "altered":
        orig_score = solve._score_dag

        def altered(*a, **kw):
            mk_mu, mk_var, smu, svar = orig_score(*a, **kw)
            return mk_mu * (1.0 + 1e-2), mk_var, smu, svar
        monkeypatch.setattr(solve, "_score_dag", altered)


@pytest.mark.parametrize("workload", ["epigenomics.cold"])
def test_dag_is_correct_unbroken(workload, fresh):
    assert _run(workload)["correct"]


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_dag_cold_fault_is_not_correct(kind, fresh):
    _dag_fault(kind, fresh)
    out = _run("epigenomics.cold")
    assert not out["correct"], out["check"]
