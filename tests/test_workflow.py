"""Workflow subsystem: StageDAG validation + composition, the stacked
per-row-statistics kernel layout (``stack_rows``), the joint solver, and
the runtime twins (WorkflowBalancer / WorkflowSim)."""
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.distributions import Drift
from repro.core.maxstat import clark_max_moments_2
from repro.kernels import ops
from repro.sched import WorkflowBalancer
from repro.sim import WorkflowSim
from repro.workflow import (DAGValidationError, Stage, StageDAG, evaluate_dag,
                            linear_edges, solve_dag, solve_dag_greedy)


def _mk_stage(name, k, seed=0, cov=(0.05, 0.4), family="normal"):
    rng = np.random.default_rng(seed)
    mus = rng.uniform(10, 40, k)
    return Stage(name, mus, mus * rng.uniform(*cov, k), family=family)


def _diamond(seed=0, family="normal"):
    stages = [_mk_stage("a", 4, seed), _mk_stage("b", 3, seed + 1,
                                                 family=family),
              _mk_stage("c", 5, seed + 2, family=family),
              _mk_stage("d", 4, seed + 3)]
    return StageDAG(stages, [("a", "b"), ("a", "c"), ("b", "d"), ("c", "d")])


class TestDAGValidation:
    def test_duplicate_names_rejected(self):
        with pytest.raises(DAGValidationError, match="duplicate"):
            StageDAG([_mk_stage("a", 2), _mk_stage("a", 2)])

    def test_unknown_edge_endpoint_rejected(self):
        with pytest.raises(DAGValidationError, match="unknown"):
            StageDAG([_mk_stage("a", 2)], [("a", "ghost")])

    def test_self_loop_rejected(self):
        with pytest.raises(DAGValidationError, match="self-loop"):
            StageDAG([_mk_stage("a", 2)], [("a", "a")])

    def test_duplicate_edge_rejected(self):
        with pytest.raises(DAGValidationError, match="duplicate edge"):
            StageDAG([_mk_stage("a", 2), _mk_stage("b", 2)],
                     [("a", "b"), ("a", "b")])

    def test_cycle_rejected_with_path(self):
        stages = [_mk_stage(n, 2) for n in "abc"]
        with pytest.raises(DAGValidationError, match="cycle detected: .*a"):
            StageDAG(stages, [("a", "b"), ("b", "c"), ("c", "a")])

    def test_depth_bound(self):
        names = [f"s{i}" for i in range(6)]
        stages = [_mk_stage(n, 2) for n in names]
        with pytest.raises(DAGValidationError, match="depth"):
            StageDAG(stages, linear_edges(names), max_depth=4)
        assert StageDAG(stages, linear_edges(names), max_depth=6).depth == 6

    def test_bad_stage_stats(self):
        with pytest.raises(DAGValidationError):
            Stage("x", np.ones(3), np.ones(2))
        with pytest.raises(DAGValidationError):
            Stage("x", np.asarray([1.0, -1.0]), np.ones(2))

    def test_topology_accessors(self):
        dag = _diamond()
        assert dag.topo_order[0] == "a" and dag.topo_order[-1] == "d"
        assert dag.sources == ("a",) and dag.sinks == ("d",)
        assert set(dag.predecessors("d")) == {"b", "c"}
        assert set(dag.successors("a")) == {"b", "c"}
        assert dag.depth == 3
        path = dag.critical_path()
        assert path[0] == "a" and path[-1] == "d" and len(path) == 3


class TestComposition:
    def test_series_adds_moments(self):
        dag = StageDAG([_mk_stage("x", 2), _mk_stage("y", 2)], [("x", "y")])
        mu, var = dag.compose_moments(jnp.asarray([3.0, 4.0]),
                                      jnp.asarray([0.5, 0.7]))
        assert np.isclose(float(mu), 7.0) and np.isclose(float(var), 1.2)

    def test_join_matches_clark(self):
        """Two independent source branches into a sink: the release is
        exactly one Clark fold of the branch completions."""
        dag = StageDAG([_mk_stage("p", 2), _mk_stage("q", 2),
                        _mk_stage("s", 2)], [("p", "s"), ("q", "s")])
        smu = jnp.asarray([10.0, 11.0, 2.0])
        svar = jnp.asarray([4.0, 1.0, 0.1])
        mu, var = dag.compose_moments(smu, svar)
        rel_mu, rel_var = clark_max_moments_2(10.0, 2.0, 11.0, 1.0)
        assert np.isclose(float(mu), float(rel_mu) + 2.0, rtol=1e-6)
        assert np.isclose(float(var), float(rel_var) + 0.1, rtol=1e-5)

    def test_jensen_bound_at_joins(self):
        """E[max] >= max E: the composed mean dominates the deterministic
        critical-path mean, with equality only as spreads vanish."""
        dag = _diamond()
        smu = jnp.asarray([5.0, 8.0, 8.0, 3.0])
        svar = jnp.asarray([1.0, 4.0, 4.0, 0.5])
        mu, _ = dag.compose_moments(smu, svar)
        assert float(mu) >= 5.0 + 8.0 + 3.0
        mu0, _ = dag.compose_moments(smu, jnp.zeros(4))
        assert float(mu0) == pytest.approx(16.0, rel=1e-6)

    def test_differentiable_and_monotone(self):
        dag = _diamond()
        smu = jnp.asarray([5.0, 8.0, 7.5, 3.0])
        svar = jnp.asarray([1.0, 2.0, 2.0, 0.5])
        g = jax.grad(lambda m: dag.compose_moments(m, svar)[0])(smu)
        assert np.all(np.isfinite(np.asarray(g)))
        assert np.all(np.asarray(g) >= -1e-6)      # makespan monotone in mus
        assert float(g[0]) == pytest.approx(1.0, abs=1e-5)  # series stage
        # (S,) batched under vmap (the solver's multi-start layout)
        mus = jnp.stack([smu, smu * 1.1])
        out = jax.vmap(lambda m: dag.compose_moments(m, svar)[0])(mus)
        assert out.shape == (2,) and float(out[1]) > float(out[0])


class TestStackedKernelLayout:
    """Per-row channel statistics through every impl and both launch modes."""

    def _problem(self, F=5, K=6, seed=0):
        rng = np.random.default_rng(seed)
        e = rng.exponential(size=(F, K))
        W = (e / e.sum(1, keepdims=True)).astype(np.float32)
        MUS = rng.uniform(10, 40, (F, K)).astype(np.float32)
        SGS = (MUS * rng.uniform(0.05, 0.35, (F, K))).astype(np.float32)
        return W, MUS, SGS

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    def test_forward_matches_per_row_loop(self, impl):
        W, MUS, SGS = self._problem()
        mu, var = ops.frontier_moments(W, MUS, SGS, num_t=512, impl=impl)
        for f in range(W.shape[0]):
            m, v = ops.frontier_moments(W[f:f + 1], MUS[f], SGS[f],
                                        num_t=512, impl=impl)
            np.testing.assert_allclose(float(mu[f]), float(m[0]), rtol=1e-5)
            np.testing.assert_allclose(float(var[f]), float(v[0]),
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    def test_fused_param_grads_match_per_row_loop(self, impl):
        W, MUS, SGS = self._problem(F=4, K=5)
        outs = ops.frontier_moments_with_grads(W, MUS, SGS, num_t=512,
                                               impl=impl, param_grads=True)
        assert len(outs) == 10
        for f in range(W.shape[0]):
            o = ops.frontier_moments_with_grads(
                W[f:f + 1], MUS[f], SGS[f], num_t=512, impl=impl,
                param_grads=True)
            for i in range(10):
                np.testing.assert_allclose(
                    np.asarray(outs[i][f]), np.asarray(o[i][0]),
                    rtol=5e-4, atol=5e-5)

    def test_chunked_path_matches_single_block(self):
        W, MUS, SGS = self._problem(F=6, K=4)
        Wb, Mb, Sb = (np.tile(a, (20, 1)) for a in (W, MUS, SGS))
        mu_c, var_c = ops.frontier_moments(Wb, Mb, Sb, num_t=256,
                                           block_f=16)
        mu_1, var_1 = ops.frontier_moments(W, MUS, SGS, num_t=256)
        np.testing.assert_allclose(np.asarray(mu_c[:6]), np.asarray(mu_1),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(var_c[:6]), np.asarray(var_1),
                                   rtol=1e-4, atol=1e-6)

    def test_stacked_drift_extra(self):
        """Per-row drift rho: the (E, F, K) extra stack through both the
        ref oracle and the interpreted kernel."""
        W, MUS, SGS = self._problem(F=3, K=4, seed=2)
        rng = np.random.default_rng(3)
        EX = rng.uniform(0.1, 0.8, (1, 3, 4)).astype(np.float32)
        mu, var = ops.frontier_moments(W, MUS, SGS, num_t=512,
                                       family=("drift", jnp.asarray(EX)))
        mu_i, var_i = ops.frontier_moments(
            W, MUS, SGS, num_t=512, impl="pallas_interpret",
            family=("drift", jnp.asarray(EX)))
        np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_i),
                                   rtol=1e-4)
        for f in range(3):
            m, _ = ops.frontier_moments(W[f:f + 1], MUS[f], SGS[f],
                                        num_t=512, family=Drift(EX[0, f]))
            np.testing.assert_allclose(float(mu[f]), float(m[0]), rtol=1e-5)

    def test_custom_vjp_per_row_cotangents(self):
        """jax.grad through stacked stats returns per-row (F, K) cotangents
        matching finite differences — no cross-row mixing."""
        W, MUS, SGS = self._problem(F=3, K=4, seed=1)
        W, MUS, SGS = jnp.asarray(W), jnp.asarray(MUS), jnp.asarray(SGS)

        def loss(W, MUS, SGS):
            mu, var = ops.frontier_moments(W, MUS, SGS, num_t=1024)
            return jnp.sum(mu * jnp.asarray([1.0, 2.0, 3.0]))

        gW, gM, gS = jax.grad(loss, argnums=(0, 1, 2))(W, MUS, SGS)
        assert gM.shape == MUS.shape and gS.shape == SGS.shape
        # FD on the largest-magnitude mus entry (f64 recompute via oracle)
        f, k = np.unravel_index(int(jnp.argmax(jnp.abs(gM))), gM.shape)
        eps = 1e-2
        coeff = [1.0, 2.0, 3.0][f]

        def row_mu(muval):
            mus_f = np.asarray(MUS[f], np.float64).copy()
            mus_f[k] = muval
            m, _ = ops.frontier_moments(np.asarray(W[f])[None, :], mus_f,
                                        np.asarray(SGS[f]), num_t=1024)
            return coeff * float(m[0])

        fd = (row_mu(float(MUS[f, k]) + eps)
              - row_mu(float(MUS[f, k]) - eps)) / (2 * eps)
        assert abs(fd - float(gM[f, k])) <= 2e-2 * max(abs(fd), 1e-3)
        # a row's stats must not receive other rows' cotangents: zero the
        # row's output weight and its stat gradient vanishes
        g0 = jax.grad(lambda M: ops.frontier_moments(
            W, M, SGS, num_t=256)[0][1] * 0.0 + jnp.sum(
                ops.frontier_moments(W, M, SGS, num_t=256)[0][:1]))(MUS)
        np.testing.assert_allclose(np.asarray(g0[1]), 0.0, atol=1e-12)
        np.testing.assert_allclose(np.asarray(g0[2]), 0.0, atol=1e-12)


class TestJointSolve:
    def test_simplex_and_padding_invariants(self):
        dag = _diamond()
        dec = solve_dag(dag, steps=40, restarts=1, num_t=256)
        for s in dag.stages:
            w = dec.weights[s.name]
            assert w.shape == (s.k,)
            assert abs(w.sum() - 1.0) < 1e-5 and (w >= 0).all()
        assert dec.family_groups == 1
        assert dec.makespan_mu > 0 and dec.makespan_var >= 0

    def test_joint_not_worse_than_greedy(self):
        dag = _diamond(seed=5)
        joint = solve_dag(dag, steps=80, restarts=2, num_t=512)
        greedy = solve_dag_greedy(dag, steps=80, restarts=2, num_t=512)
        # identical evaluator on both: the joint objective can only win
        assert joint.makespan_mu <= greedy.makespan_mu * (1 + 1e-3)

    def test_warm_start_stays_near_solution(self):
        dag = _diamond(seed=2)
        dec = solve_dag(dag, steps=60, restarts=1, num_t=256)
        dec2 = solve_dag(dag, steps=10, restarts=0, num_t=256,
                         warm_start=dec.weights)
        assert dec2.makespan_mu <= dec.makespan_mu * 1.01

    def test_mixed_families_group_per_dist(self):
        dag = _diamond(seed=3, family="lognormal")  # b, c lognormal; a, d normal
        dec = solve_dag(dag, steps=30, restarts=0, num_t=256)
        assert dec.family_groups == 2
        ev = evaluate_dag(dag, dec.weights, num_t=512)
        assert ev.makespan_mu == pytest.approx(dec.makespan_mu, rel=0.05)

    def test_risk_lam_reports_fragility(self):
        from repro.core.bayes import nig_init, nig_update_batch

        dag = _diamond(seed=4)
        posteriors = {}
        rng = np.random.default_rng(0)
        for s in dag.stages:
            nig = nig_init(s.k, m0=float(np.mean(s.mus)))
            for _ in range(5):
                rates = rng.normal(s.mus, s.sigmas).astype(np.float32)
                nig = nig_update_batch(nig, jnp.asarray(rates),
                                       jnp.ones(s.k, jnp.float32))
            posteriors[s.name] = nig
        dec = solve_dag(dag, steps=30, restarts=0, num_t=256,
                        risk_lam=0.5, posteriors=posteriors)
        assert dec.method == "pgd-dag-joint-risk"
        assert dec.fragility is not None and dec.fragility > 0
        assert dec.relative_fragility < 1.0

    def test_evaluate_matches_manual_composition(self):
        """The shared evaluator = per-stage oracle moments + compose."""
        from repro.core.maxstat import max_moments_quad_w

        dag = _diamond(seed=6)
        weights = {s.name: np.full(s.k, 1.0 / s.k) for s in dag.stages}
        ev = evaluate_dag(dag, weights, num_t=2048)
        smu, svar = [], []
        for s in dag.stages:
            m, v = max_moments_quad_w(weights[s.name], s.mus, s.sigmas,
                                      num=2048)
            smu.append(float(m))
            svar.append(float(v))
        mk_mu, mk_var = dag.compose_moments(jnp.asarray(smu),
                                            jnp.asarray(svar))
        assert ev.makespan_mu == pytest.approx(float(mk_mu), rel=5e-3)
        assert ev.makespan_var == pytest.approx(float(mk_var), rel=5e-2,
                                                abs=1e-3)


class TestMultiFidelity:
    """PR 8: the fidelity ladder, candidate pruning, and incremental
    (dirty-set) re-solves. The bitwise contracts here are pinned in
    docs/INVARIANTS.md."""

    def test_ladder_final_pick_matches_full_fidelity(self):
        """Coarse scores are triage-only: running the whole ladder at the
        solve fidelity (no coarse rung, no prune, no early stop) must land
        within 1e-3 relative composed makespan of the default ladder."""
        dag = _diamond(seed=12)
        mf = solve_dag(dag, steps=60, restarts=1, num_t=512)
        full = solve_dag(dag, steps=60, restarts=1, num_t=512,
                         presolve_num_t=512, prune_margin=None,
                         plateau_patience=None)
        assert mf.makespan_mu == pytest.approx(full.makespan_mu, rel=1e-3)

    def test_coarse_rung_ranking_resolution(self):
        """The coarse rung's MOMENTS are biased vs the fine rung (that's why
        they never decide the winner) but by far less than the margins the
        triage prunes on."""
        dag = _diamond(seed=12)
        w = {s.name: np.full(s.k, 1.0 / s.k) for s in dag.stages}
        coarse = evaluate_dag(dag, w, num_t=128)
        fine = evaluate_dag(dag, w, num_t=2048)
        gap = abs(coarse.makespan_mu - fine.makespan_mu) / fine.makespan_mu
        assert gap < 1e-3

    def test_profile_attributes_ladder_phases(self):
        dag = _diamond(seed=13)
        dec = solve_dag(dag, steps=30, restarts=1, num_t=256)
        prof = dec.profile
        assert {"starts", "presolve", "triage", "refine",
                "final_score"} <= set(prof["phase_us"])
        assert prof["presolve_num_t"] == 128      # min(default 128, num_t)
        assert prof["eval_num_t"] == 2048         # max(num_t, 2048)
        assert 1 <= prof["survivors"] <= prof["pool"]
        assert 1 <= prof["refine_steps_run"] <= 30

    def test_plateau_early_stop_saves_steps(self):
        """A huge plateau_tol makes every post-warmup step a stall, so the
        refine must cut out right after the warmup + patience window instead
        of running the full budget; patience=None restores the fixed count."""
        dag = _diamond(seed=13)
        stopped = solve_dag(dag, steps=60, restarts=0, num_t=128,
                            plateau_tol=0.5, plateau_patience=2)
        fixed = solve_dag(dag, steps=60, restarts=0, num_t=128,
                          plateau_patience=None)
        assert stopped.profile["refine_steps_run"] < 60
        assert fixed.profile["refine_steps_run"] == 60

    def test_empty_dirty_is_bitwise_noop(self, monkeypatch):
        """An empty dirty set returns the warm split verbatim from one
        forward evaluation — launching PGD at all is the bug."""
        import repro.workflow.solve as solve_mod

        dag = _diamond(seed=14)
        dec = solve_dag(dag, steps=30, restarts=0, num_t=256)

        def boom(*a, **k):
            raise AssertionError("PGD launched on an empty dirty set")

        monkeypatch.setattr(solve_mod, "_pgd_phase", boom)
        dec2 = solve_dag(dag, steps=30, restarts=0, num_t=256,
                         warm_start=dec.weights, dirty=set())
        assert dec2.method == "pgd-dag-noop"
        assert dec2.profile["noop"] and dec2.profile["starts"] == 0
        for s in dag.stages:
            assert np.array_equal(dec.weights[s.name], dec2.weights[s.name])
        assert dec2.makespan_mu == pytest.approx(dec.makespan_mu, rel=5e-3)

    def test_single_dirty_stage_freezes_other_rows_bitwise(self):
        dag = _diamond(seed=15)
        dec = solve_dag(dag, steps=30, restarts=0, num_t=256)
        dec2 = solve_dag(dag, steps=20, restarts=0, num_t=256,
                         warm_start=dec.weights, dirty={"b"})
        assert dec2.method == "pgd-dag-joint-inc"
        for s in dag.stages:
            if s.name == "b":
                continue
            assert np.array_equal(dec.weights[s.name], dec2.weights[s.name]), \
                f"frozen stage {s.name} moved"

    @pytest.mark.parametrize("lam_var", [0.0, 0.5])
    def test_warm_dirty_winner_is_the_pools_best(self, monkeypatch, lam_var):
        """The winner is indexed from the final score's host copies: its
        frozen rows are the warm rows bitwise, and it is the final pool's
        best on the objective, with the pool's own float32 moments."""
        import repro.workflow.solve as solve_mod

        dag = _diamond(seed=15)
        dec = solve_dag(dag, steps=30, restarts=0, num_t=256)
        scored = []
        score_dag = solve_mod._score_dag

        def record(*a, **k):
            out = score_dag(*a, **k)
            scored.append((a[4], out))
            return out

        monkeypatch.setattr(solve_mod, "_score_dag", record)
        dec2 = solve_dag(dag, steps=20, restarts=1, num_t=256,
                         lam_var=lam_var, warm_start=dec.weights,
                         dirty={"b"})
        for s in dag.stages:
            if s.name != "b":
                assert np.array_equal(dec.weights[s.name],
                                      dec2.weights[s.name])
        cands, (mk_mu, mk_var, smu, svar) = (
            jax.device_get(x) for x in scored[-1])
        assert cands.shape[0] == dec2.profile["pool"]
        score = (np.asarray(mk_mu, np.float64)
                 + lam_var * np.asarray(mk_var, np.float64))
        best = int(np.argmin(score))
        assert dec2.makespan_mu + lam_var * dec2.makespan_var == score.min()
        if lam_var == 0.0:
            assert dec2.makespan_mu == score.min()
        assert dec2.makespan_mu == float(mk_mu[best])
        assert dec2.makespan_var == float(mk_var[best])
        np.testing.assert_array_equal(dec2.stage_mu, smu[best])
        np.testing.assert_array_equal(dec2.stage_var, svar[best])
        for i, s in enumerate(dag.stages):
            np.testing.assert_array_equal(dec2.weights[s.name],
                                          cands[best, i, :s.k])

    def test_dirty_validation(self):
        dag = _diamond(seed=16)
        with pytest.raises(ValueError, match="warm_start"):
            solve_dag(dag, steps=5, num_t=128, dirty={"b"})
        dec = solve_dag(dag, steps=5, restarts=0, num_t=128)
        with pytest.raises(KeyError, match="ghost"):
            solve_dag(dag, steps=5, num_t=128, warm_start=dec.weights,
                      dirty={"ghost"})

    def test_greedy_rides_the_same_knobs(self):
        dag = _diamond(seed=15)
        base = solve_dag_greedy(dag, steps=20, restarts=0, num_t=256)
        inc = solve_dag_greedy(dag, steps=10, restarts=0, num_t=256,
                               presolve_num_t=128,
                               warm_start=base.weights, dirty={"c"})
        for s in dag.stages:
            if s.name == "c":
                continue
            assert np.array_equal(base.weights[s.name], inc.weights[s.name])
        with pytest.raises(ValueError, match="warm_start"):
            solve_dag_greedy(dag, steps=5, num_t=128, dirty={"c"})

    def test_autotune_keys_separate_fidelity_rungs(self):
        """Coarse and fine rungs must resolve distinct autotune entries — a
        silicon sweep at one fidelity can never shadow another's plan."""
        from repro.kernels.autotune import _key

        coarse = _key(8, 64, 128, "xla", False, stacked=True)
        fine = _key(8, 64, 2048, "xla", False, stacked=True)
        assert coarse != fine
        assert "T128" in coarse and "T2048" in fine


def _parity_module():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "fixed_stage_parity.py")
    spec = importlib.util.spec_from_file_location("fixed_stage_parity", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_PARITY = _parity_module()


class TestFixedStages:
    """Single-channel stages are composed as constants, never PGD rows:
    the solver does the arithmetic it did when they were rows."""

    @pytest.mark.parametrize("impl", _PARITY.IMPLS)
    @pytest.mark.parametrize("case", [c[0] for c in _PARITY.CASES])
    def test_decisions_match_the_solver_with_fixed_rows(self, case, impl):
        """Decisions equal those of the solver that carried single-channel
        stages as PGD rows (``tests/fixtures/fixed_stage_parity.py`` says
        how the fixture was made): bitwise on the XLA path, where each
        row's moments do not depend on the launch's other rows, and for a
        DAG with no single-channel stage, which runs the same program.

        Under the Pallas interpreter a launch's float32 sums follow the
        shape of its block, so a stage's moments move with the rows beside
        it: the fixture's own solver, given ``block_f=2``, moves its
        ``stage_var`` by up to 2.2e-5 of itself. The moments are held to
        1e-6 of themselves, ``stage_var`` to 1e-6 of the stage mean's
        square (the scale a variance lives on), the splits to 1e-5."""
        with open(_PARITY.PATH) as f:
            want = json.load(f)[case][impl]
        got = _PARITY.decision(_PARITY.solve(case, impl))
        if impl == "xla" or all(len(w) > 1 for w in want["weights"].values()):
            assert got == want
            return
        for key in ("makespan_mu", "makespan_var"):
            assert got[key] == pytest.approx(want[key], rel=1e-6)
        np.testing.assert_allclose(got["stage_mu"], want["stage_mu"],
                                   rtol=1e-6)
        gap = np.abs(np.subtract(got["stage_var"], want["stage_var"]))
        assert np.all(gap <= 1e-6 * np.square(want["stage_mu"])), gap
        for name, w in want["weights"].items():
            np.testing.assert_allclose(got["weights"][name], w, rtol=0,
                                       atol=1e-5)

    def test_all_single_channel_dag_makes_no_pgd_launch(self, monkeypatch):
        """With no split that can move, both PGD phases are skipped and
        the all-ones split comes back, scored at evaluation fidelity."""
        import repro.workflow.solve as solve_mod

        def boom(*a, **k):
            raise AssertionError("PGD launched with no movable stage")

        monkeypatch.setattr(solve_mod, "_pgd_phase", boom)
        stages = [_mk_stage(n, 1, seed=i) for i, n in enumerate("abc")]
        dag = StageDAG(stages, [("a", "b"), ("a", "c")])
        dec = solve_dag(dag, steps=8, restarts=1, num_t=32, eval_num_t=64)
        assert {n: w.tolist() for n, w in dec.weights.items()} == \
            {n: [1.0] for n in "abc"}
        prof = dec.profile
        assert prof["fixed_stages"] == 3
        assert [e["phase"] for e in prof["launches"]] == ["triage",
                                                          "final_score"]
        assert prof["presolve_steps_run"] == prof["refine_steps_run"] == 0
        ev = evaluate_dag(dag, dec.weights, num_t=64)
        assert dec.makespan_mu == pytest.approx(ev.makespan_mu, rel=1e-6)
        assert dec.makespan_var == pytest.approx(ev.makespan_var, rel=1e-6)

    @pytest.mark.parametrize("dirty", [("c",), ("b", "c"), ("b",)],
                             ids=["movable", "both", "fixed-only"])
    def test_dirty_resolve_passes_frozen_rows_bitwise(self, dirty):
        """A re-solve of some stages of a DAG with single-channel stages:
        every stage outside the dirty set keeps its warm split bitwise,
        and every single-channel stage keeps [1]."""
        dag = _PARITY.dag("chain")
        dec = solve_dag(dag, steps=12, restarts=1, num_t=32)
        dec2 = solve_dag(dag, steps=12, restarts=1, num_t=32,
                         warm_start=dec.weights, dirty=set(dirty))
        assert dec2.method == "pgd-dag-joint-inc"
        assert dec2.profile["fixed_stages"] == 2
        for s in dag.stages:
            if s.k == 1:
                assert dec2.weights[s.name].tolist() == [1.0]
            if s.name not in dirty:
                assert np.array_equal(dec.weights[s.name],
                                      dec2.weights[s.name]), s.name


class TestIncrementalBalancer:
    """WorkflowBalancer's fragility-gated dirty sets (PR 8)."""

    def _spied(self, monkeypatch):
        """Wrap workflow.solve.solve_dag, recording each call's dirty= —
        the balancer imports it lazily inside weights(), so patching the
        solve module intercepts every solver call."""
        import repro.workflow.solve as solve_mod

        calls = []
        real = solve_mod.solve_dag

        def spy(dag, **kw):
            calls.append(kw.get("dirty"))
            return real(dag, **kw)

        monkeypatch.setattr(solve_mod, "solve_dag", spy)
        return calls

    def _bal(self, dag):
        # risk_lam > 0 makes the composed fragility ride every solve, and
        # the huge refresh_target_rel keeps the incremental gate open
        return WorkflowBalancer(dag, refresh_every=1, pgd_steps=10,
                                num_t=128, restarts=0, family="normal",
                                risk_lam=1e-6, refresh_target_rel=100.0)

    def test_drifted_stage_dirties_only_itself(self, monkeypatch):
        calls = self._spied(monkeypatch)
        dag = _diamond(seed=17)
        bal = self._bal(dag)

        w0 = bal.weights()
        assert calls == [None]          # first solve is always full

        w0b = bal.weights()
        assert len(calls) == 1          # no drift: empty dirty, no solver call
        for n in w0:
            assert np.array_equal(w0[n], w0b[n])

        # move ONE stage's posterior far past dirty_tol; the others see no
        # observations and stay inside their snapshots
        for _ in range(4):
            bal.observe({"b": np.full(3, 5.0)}, {"b": w0["b"]})
        w1 = bal.weights()
        assert calls[-1] == {"b"}
        for n in w0:
            if n != "b":
                assert np.array_equal(w0[n], w1[n]), f"frozen {n} moved"

    def test_state_dict_round_trips_snapshots(self, monkeypatch):
        calls = self._spied(monkeypatch)
        dag = _diamond(seed=18)
        bal = self._bal(dag)
        w0 = bal.weights()
        sd = bal.state_dict()
        assert set(sd["solve_stats"]) == set(dag.names)
        assert set(sd["solve_fams"]) == set(dag.names)

        b2 = WorkflowBalancer.from_state_dict(sd, dag)
        n_calls = len(calls)
        w2 = b2.weights()
        # the restored replica inherits the snapshots: nothing drifted, so
        # its first tick is the cached split with NO solver call — the same
        # incremental decision the original would have made
        assert len(calls) == n_calls
        for n in w0:
            assert np.array_equal(w0[n], w2[n])


class TestComposeMC:
    """Satellite acceptance: composed (mu, var) vs large-sample simulation."""

    def _random_dag(self, seed=11):
        """Random 5-stage DAG: seeded structure over a topological order."""
        rng = np.random.default_rng(seed)
        names = [f"s{i}" for i in range(5)]
        stages = [_mk_stage(n, int(rng.integers(2, 6)), seed + i,
                            cov=(0.1, 0.3))
                  for i, n in enumerate(names)]
        edges = []
        for j in range(1, 5):
            preds = [i for i in range(j) if rng.random() < 0.6] or [j - 1]
            edges += [(names[i], names[j]) for i in preds]
        return StageDAG(stages, edges)

    @pytest.mark.mc_oracle
    def test_composed_moments_match_simulation(self):
        dag = self._random_dag()
        weights = {s.name: np.full(s.k, 1.0 / s.k) for s in dag.stages}
        ev = evaluate_dag(dag, weights, num_t=4096)

        # vectorized 1e6-sample DAG simulation straight from the stage
        # completion model (normal per-channel rates, release = max preds)
        N = 1_000_000
        rng = np.random.default_rng(3)
        comp = {}
        for s in dag.stages:
            w = weights[s.name]
            rates = rng.normal(s.mus, s.sigmas, size=(N, s.k))
            dur = (w * rates).max(axis=1)
            rel = 0.0
            preds = dag.predecessors(s.name)
            if preds:
                rel = comp[preds[0]]
                for p in preds[1:]:
                    rel = np.maximum(rel, comp[p])
                # Jensen sanity at every join: E[max] >= max E
                if len(preds) > 1:
                    assert rel.mean() >= max(comp[p].mean()
                                             for p in preds) - 1e-9
            comp[s.name] = rel + dur
        mk = comp[dag.sinks[0]]
        for p in dag.sinks[1:]:
            mk = np.maximum(mk, comp[p])
        # tolerance: mu is tight (series sums exact, Clark joins near-exact
        # for independent branches); var absorbs the shared-ancestor
        # dependence the composition ignores
        assert abs(ev.makespan_mu - mk.mean()) / mk.mean() < 0.02
        assert abs(ev.makespan_var - mk.var()) / mk.var() < 0.25


class TestWorkflowRuntime:
    def test_workflow_sim_precedence_and_reproducibility(self):
        dag = _diamond(seed=7)
        weights = {s.name: np.full(s.k, 1.0 / s.k) for s in dag.stages}
        sim = WorkflowSim.from_dag(dag, seed=3)
        mk, comp, durs = sim.run_dag_step(dag, weights, rng=5)
        for u, v in dag.edges:
            assert comp[v] >= comp[u]
        assert mk == pytest.approx(max(comp[n] for n in dag.sinks))
        sim2 = WorkflowSim.from_dag(dag, seed=3)
        mk2, _, _ = sim2.run_dag_step(dag, weights, rng=5)
        assert mk == pytest.approx(mk2)

    def test_workflow_balancer_ticks_and_cache(self):
        dag = _diamond(seed=8)
        sim = WorkflowSim.from_dag(dag, seed=4)
        bal = WorkflowBalancer(dag, refresh_every=4, pgd_steps=15,
                               num_t=128, restarts=0)
        w0 = bal.weights()
        assert set(w0) == set(dag.names)
        mk, comp, durs = sim.run_dag_step(dag, w0)
        bal.observe(durs, w0)                    # obs_count -> 1
        first_w = bal.weights()                  # fresh solve at obs 1
        first = bal.last_decision
        for _ in range(2):                       # obs 2, 3: inside cadence
            mk, comp, durs = sim.run_dag_step(dag, bal.weights())
            bal.observe(durs, bal.weights())
            bal.weights()
        assert bal.last_decision is first        # cached, no re-solve
        mk, comp, durs = sim.run_dag_step(dag, bal.weights())
        bal.observe(durs, bal.weights())         # obs_count -> 4 == cadence
        bal.weights()                            # fresh joint solve
        assert bal.last_decision is not first

    def test_workflow_balancer_min_weight_floor(self):
        dag = _diamond(seed=9)
        bal = WorkflowBalancer(dag, pgd_steps=10, num_t=128, restarts=0,
                               min_weight=0.05)
        for w in bal.weights().values():
            assert (w >= 0.05 - 1e-9).all()
            assert abs(w.sum() - 1.0) < 1e-9

    def test_stack_rows_groups_by_family(self):
        from repro.workflow.solve import stack_rows

        rows = [(np.array([1.0, 2.0]), np.array([0.1, 0.2]), "normal"),
                (np.array([1.0, 2.0, 3.0]), np.array([0.1, 0.2, 0.3]),
                 "lognormal"),
                (np.array([2.0, 1.0]), np.array([0.2, 0.1]), "normal")]
        groups, mask, kmax = stack_rows(rows)
        assert kmax == 3
        by = {g.dist_id: g for g in groups}
        assert set(by) == {"normal", "lognormal"}
        assert by["normal"].idx == (0, 2)       # original row positions
        assert by["lognormal"].idx == (1,)
        # ragged K pads with zeros; the mask marks the real channels
        np.testing.assert_array_equal(mask, [[1, 1, 0], [1, 1, 1],
                                             [1, 1, 0]])
        assert by["normal"].mus.shape == (2, 3)
        np.testing.assert_array_equal(by["normal"].mus[:, 2], [0.0, 0.0])
        assert by["normal"].extra.shape[1:] == (2, 3)

    def test_stack_rows_pinned_kmax_and_overflow(self):
        from repro.workflow.solve import stack_rows

        rows = [(np.array([1.0, 2.0, 3.0]), np.array([0.1, 0.2, 0.3]),
                 "normal")]
        # a serving engine pins kmax so jit keys stay stable across ticks
        _, mask, kmax = stack_rows(rows, kmax=5)
        assert kmax == 5 and mask.shape == (1, 5)
        with pytest.raises(ValueError, match="kmax"):
            stack_rows(rows, kmax=2)


class TestNoDeprecatedNormalShim:
    def test_no_in_repo_module_imports_core_normal(self):
        """The deprecated ``core.normal`` shim stays one release for
        external callers, but nothing inside the package may ride it.

        Enforced by lint rule RPA050 (AST-based, so string mentions in
        docstrings/comments don't false-positive the way the old text scan
        did); this test pins the rule to the real source tree.
        """
        import pathlib

        import repro
        from repro.analysis import run_paths

        root = pathlib.Path(repro.__file__).parent
        findings = run_paths([str(root)], select=["RPA050"])
        assert not findings, [f.format() for f in findings]
