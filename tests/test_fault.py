"""Fault-tolerance tier (the ``fault`` marker; scripts/ci.sh runs it under
REPRO_SANITIZE=1 on every tier).

Acceptance anchors:
  * kill/restore tick parity — a balancer / workflow balancer / serving
    batcher rebuilt from a ``save_pipeline`` manifest produces a next tick
    BITWISE identical to the survivor's (ckpt/store.py's contract);
  * churn schedules (fail / throttle / recover mid-trace) flow from the sim
    into the deciders: a failed channel draws zero share on the next tick
    and is re-admitted after recovery;
  * ``resolve_inflight`` prices sunk work: dead channels get exactly zero,
    finished jobs solve to zero, and a firm adaptive-refresh solve skips
    the PGD (the warm start IS the answer);
  * checkpoint robustness — corrupt/empty/missing LATEST pointers fall back
    to the newest complete step, and template/checkpoint divergence raises
    a ValueError naming the leaf and both shapes (the old bare assert
    vanished under ``python -O``);
  * the chaos harness composes all of the above and verifies parity
    continuously.
"""
import json
import os

import numpy as np
import pytest

from repro.ckpt import (CheckpointManager, latest_step, restore,
                        restore_pipeline, save, save_pipeline)
from repro.kernels import autotune
from repro.sched import StragglerPolicy, UncertaintyAwareBalancer
from repro.sched.balancer import WorkflowBalancer
from repro.sim import Channel, ClusterSim
from repro.sim.chaos import run_chaos_trace
from repro.sim.cluster import WorkflowSim
from repro.workflow.dag import Stage, StageDAG, linear_edges

pytestmark = pytest.mark.fault


def _seeded_balancer(k=4, seed=0, **kw):
    kw.setdefault("lam", 0.05)
    kw.setdefault("pgd_steps", 40)
    kw.setdefault("explore", 0.0)
    b = UncertaintyAwareBalancer(num_channels=k, **kw)
    rng = np.random.default_rng(seed)
    for _ in range(5):
        b.observe(rng.uniform(8, 30, k), np.full(k, 1.0 / k))
    return b


def _dag(k=3):
    rng = np.random.default_rng(7)
    stages = [Stage("a", rng.uniform(10, 30, k), rng.uniform(1, 4, k)),
              Stage("b", rng.uniform(10, 30, k), rng.uniform(1, 4, k))]
    return StageDAG(stages, linear_edges(["a", "b"]))


def _engine_templates():
    """Two mixed-family templates for the serving-engine fault tests."""
    wf = StageDAG([
        Stage("a", mus=[1.0, 1.5], sigmas=[0.2, 0.3]),
        Stage("b", mus=[2.0, 2.6, 3.2], sigmas=[0.3, 0.4, 0.5]),
    ], edges=linear_edges(["a", "b"]))
    fan = StageDAG([
        Stage("src", mus=[1.2, 1.7], sigmas=[0.25, 0.3],
              family="lognormal"),
        Stage("left", mus=[2.1, 2.8], sigmas=[0.4, 0.5],
              family="lognormal"),
        Stage("right", mus=[1.9, 2.5], sigmas=[0.35, 0.45],
              family="lognormal"),
    ], edges=[("src", "left"), ("src", "right")])
    return {"wf": wf, "fan": fan}


def _seeded_workflow_balancer(dag, seed=0, **kw):
    kw.setdefault("pgd_steps", 30)
    wb = WorkflowBalancer(dag=dag, **kw)
    rng = np.random.default_rng(seed)
    w = {s.name: np.full(s.k, 1.0 / s.k) for s in dag.stages}
    for _ in range(4):
        wb.observe({s.name: rng.uniform(8, 30, s.k) for s in dag.stages}, w)
    return wb


class TestKillRestoreParity:
    """ckpt/store.py's contract: the restored replica's next tick is
    bitwise identical to the survivor's."""

    def test_balancer_tick_parity(self, tmp_path):
        b = _seeded_balancer()
        save_pipeline(str(tmp_path), 3, b)
        w_survivor = b.weights()
        b2, inflight, meta = restore_pipeline(str(tmp_path))
        assert inflight is None and meta["step"] == 3
        np.testing.assert_array_equal(w_survivor, b2.weights())
        # posteriors came along too: the tick after next also agrees
        obs = np.array([12.0, 25.0, 18.0, 30.0])
        b.observe(obs, w_survivor)
        b2.observe(obs, w_survivor)
        np.testing.assert_array_equal(b.weights(), b2.weights())

    def test_workflow_balancer_tick_parity(self, tmp_path):
        dag = _dag()
        wb = _seeded_workflow_balancer(dag)
        wb.handle_failure("a", 1)   # failure sets must survive the crash too
        save_pipeline(str(tmp_path), 1, wb)
        w_survivor = wb.weights()
        wb2, _, _ = restore_pipeline(str(tmp_path), dag=dag)
        w_replica = wb2.weights()
        assert wb2.failed_channels() == {"a": [1]}
        for n in w_survivor:
            np.testing.assert_array_equal(w_survivor[n], w_replica[n])
        assert w_replica["a"][1] == 0.0

    def test_workflow_kind_requires_dag(self, tmp_path):
        save_pipeline(str(tmp_path), 1, _seeded_workflow_balancer(_dag()))
        with pytest.raises(ValueError, match="dag"):
            restore_pipeline(str(tmp_path))

    def test_partitioned_batcher_tick_parity(self, tmp_path):
        from repro.serve.engine import PartitionedBatcher, ReplicaGroup

        groups = [ReplicaGroup(name=f"g{i}") for i in range(3)]
        pb = PartitionedBatcher(groups, lam=0.02, seed=5)
        prompts = np.zeros((18, 4), np.int32)
        for _ in range(2):
            pb.run_batch(prompts)
        # the manifest carries the balancer; the sim world rides inflight
        save_pipeline(str(tmp_path), 2, pb.balancer,
                      inflight={"sim": pb.sim.state_dict()})
        join_sv, counts_sv = pb.run_batch(prompts)
        bal2, inflight, _ = restore_pipeline(str(tmp_path))
        pb2 = PartitionedBatcher(groups)
        pb2.balancer = bal2
        pb2.sim = ClusterSim.from_state_dict(inflight["sim"])
        join_rp, counts_rp = pb2.run_batch(prompts)
        assert join_sv == join_rp
        np.testing.assert_array_equal(counts_sv, counts_rp)

    def test_workflow_engine_kill_restore_tick_parity(self, tmp_path):
        """Engine-level kill/restore through the PR 7 manifest with
        instances IN FLIGHT: the restored engine's next tick — admissions,
        stacked solves, per-instance splits, retirements — is bitwise
        identical to the survivor's."""
        from repro.serve import WorkflowEngine

        templates = _engine_templates()
        eng = WorkflowEngine(templates, max_live=4, settle_steps=2,
                             num_t=128, seed=7)
        for i in range(6):   # more than max_live: the queue rides too
            eng.submit("wf" if i % 2 else "fan", deadline=6.0)
        eng.tick()
        assert eng.live_count > 0          # mid-flight, not a cold engine
        assert eng.queue_depth > 0         # backpressured requests ride too
        save_pipeline(str(tmp_path), eng.tick_count, eng)
        survivor = eng.tick()              # the would-be survivor's tick
        eng2, _, _ = restore_pipeline(str(tmp_path), templates=templates)
        replica = eng2.tick()
        assert survivor == replica
        for iid, inst in eng._live.items():
            for name, w in inst.weights.items():
                np.testing.assert_array_equal(
                    w, eng2._live[iid].weights[name])

    def test_engine_kind_checkpoint_needs_templates(self, tmp_path):
        from repro.serve import WorkflowEngine

        eng = WorkflowEngine(_engine_templates(), num_t=128)
        save_pipeline(str(tmp_path), 1, eng)
        with pytest.raises(ValueError, match="templates"):
            restore_pipeline(str(tmp_path))

    def test_workflow_sim_churn_schedule(self):
        dag = StageDAG([
            Stage("s1", mus=[10.0, 14.0], sigmas=[1.0, 1.5]),
            Stage("s2", mus=[12.0, 16.0], sigmas=[1.2, 1.8]),
        ], edges=linear_edges(["s1", "s2"]))
        sim = WorkflowSim.from_dag(dag, seed=0)
        sim.schedule_churn(2, "fail", stage="s1", idx=0)
        sim.schedule_churn(2, "set_load", value=1.5)    # stage=None: all
        sim.schedule_churn(3, "recover", stage="s1", idx=0)
        sim.tick()
        assert not sim.stage_sims["s1"].channels[0].failed
        sim.tick()          # step 2: the fail and the broadcast load fire
        assert sim.stage_sims["s1"].channels[0].failed
        assert all(s.load_factor == 1.5 for s in sim.stage_sims.values())
        sim.tick()
        assert not sim.stage_sims["s1"].channels[0].failed
        with pytest.raises(ValueError, match="action"):
            sim.schedule_churn(1, "explode")
        with pytest.raises(ValueError, match="stage"):
            sim.schedule_churn(1, "fail", idx=0)        # fail needs a stage
        with pytest.raises(ValueError, match="value"):
            sim.schedule_churn(1, "throttle", stage="s1", idx=0)

    def test_workflow_sim_state_round_trip_with_pending_churn(self):
        dag = StageDAG([Stage("s", mus=[10.0, 14.0], sigmas=[1.0, 1.5])])
        sim = WorkflowSim.from_dag(dag, seed=3)
        sim.schedule_churn(2, "throttle", stage="s", idx=1, value=2.0)
        sim.tick()
        sim2 = WorkflowSim.from_state_dict(sim.state_dict())
        m1, _, d1 = sim.run_dag_step(dag, {"s": np.array([0.6, 0.4])})
        m2, _, d2 = sim2.run_dag_step(dag, {"s": np.array([0.6, 0.4])})
        assert m1 == m2                    # rng stream AND churn both rode
        np.testing.assert_array_equal(d1["s"], d2["s"])
        # the pending throttle fired at step 2 in BOTH worlds (mu doubled)
        assert sim.stage_sims["s"].channels[1].mu == pytest.approx(28.0)
        assert sim2.stage_sims["s"].channels[1].mu == pytest.approx(28.0)

    def test_workflow_chaos_trace_parity(self):
        from repro.sim.chaos import run_workflow_chaos_trace

        dag = StageDAG([
            Stage("s1", mus=[10.0, 14.0, 18.0], sigmas=[1.0, 1.5, 2.0]),
            Stage("s2", mus=[12.0, 16.0], sigmas=[1.2, 1.8]),
        ], edges=linear_edges(["s1", "s2"]))
        res = run_workflow_chaos_trace(
            dag, ticks=6, kill_every=3, seed=1,
            churn=[(2, "fail", "s1", 0, None),
                   (5, "recover", "s1", 0, None)],
            verify_parity=True)
        assert res.kills == 1 and res.parity_checks == 1
        assert len(res.joins) == 6 and all(j > 0 for j in res.joins)
        assert res.final_failed == []      # recovered before the end

    def test_chaos_trace_verifies_parity_continuously(self):
        res = run_chaos_trace(num_channels=5, ticks=9, kill_every=3,
                              churn=[(4, "fail", 1), (7, "recover", 1)],
                              seed=2, verify_parity=True)
        assert res.kills == 2 and res.parity_checks == 2
        assert len(res.joins) == 9 and all(j > 0 for j in res.joins)
        assert res.final_failed == []       # recovered before the end
        s = res.summary()
        assert s["parity_checks"] == 2 and s["mean_join"] > 0

    def test_chaos_trace_defective_fleet(self):
        """Crash cycles + retry physics: the geometric retry draws ride the
        snapshotted rng stream, so parity holds for defective fleets too."""
        res = run_chaos_trace(num_channels=4, ticks=6, kill_every=2,
                              dist="defective", seed=3, verify_parity=True)
        assert res.kills == 2 and res.parity_checks == 2


class TestChurnSchedules:
    def test_fail_then_recover_round_trip(self):
        sim = ClusterSim.heterogeneous(3, seed=1)
        sim.schedule_churn(2, "fail", 1)
        sim.schedule_churn(3, "recover", 1)
        w = np.full(3, 1.0 / 3)
        _, d1 = sim.run_step(w)
        assert (d1 > 0).all()
        _, d2 = sim.run_step(w)            # event fires BEFORE the draws
        assert d2[1] == 0.0 and d2[0] > 0 and d2[2] > 0
        _, d3 = sim.run_step(w)
        assert (d3 > 0).all()

    def test_throttle_inflates_one_channel(self):
        mk = lambda: ClusterSim([Channel(mu=20.0, sigma=1e-6)
                                 for _ in range(2)], seed=4)
        base = mk()
        slow = mk()
        slow.schedule_churn(1, "throttle", 0, 3.0)
        _, db = base.run_step([0.5, 0.5])
        _, ds = slow.run_step([0.5, 0.5])
        assert ds[0] > 2.0 * db[0]
        np.testing.assert_allclose(ds[1], db[1])

    def test_schedule_churn_validates(self):
        sim = ClusterSim.heterogeneous(2, seed=0)
        with pytest.raises(ValueError, match="action"):
            sim.schedule_churn(1, "explode", 0)
        with pytest.raises(ValueError, match="idx"):
            sim.schedule_churn(1, "fail")
        with pytest.raises(ValueError, match="value"):
            sim.schedule_churn(1, "throttle", 0)

    def test_sim_state_dict_replays_bitwise(self):
        sim = ClusterSim.heterogeneous(4, seed=6, dist="defective")
        sim.schedule_churn(4, "fail", 2)
        w = np.full(4, 0.25)
        for _ in range(2):
            sim.run_step(w)
        clone = ClusterSim.from_state_dict(sim.state_dict())
        for _ in range(3):                  # crosses the queued churn event
            t1, d1 = sim.run_step(w)
            t2, d2 = clone.run_step(w)
            assert t1 == t2
            np.testing.assert_array_equal(d1, d2)
        assert sim.channels[2].failed and clone.channels[2].failed


class TestStragglerSimWiring:
    def _policy(self, k=3, seed=0):
        b = UncertaintyAwareBalancer(k, lam=0.01, pgd_steps=40, explore=0.0)
        pol = StragglerPolicy(b, z_threshold=4.0)
        rng = np.random.default_rng(seed)
        for _ in range(6):
            pol.record(rng.uniform(9, 11, k), np.full(k, 1.0 / k))
        return pol

    def test_soft_fail_zero_weight_then_readmit(self):
        pol = self._policy()
        assert (pol.weights() > 0).all()
        pol.fail(1, remove=False)
        w = pol.weights()
        assert w[1] == 0.0 and abs(w.sum() - 1.0) < 1e-9
        pol.recover(1)
        assert pol.weights()[1] > 0.0      # posterior survived the outage

    def test_fail_propagates_to_bound_sim(self):
        pol = self._policy()
        sim = ClusterSim.heterogeneous(3, seed=2)
        pol.bind_sim(sim)
        pol.fail(2, remove=False)
        assert sim.channels[2].failed
        pol.recover(2)
        assert not sim.channels[2].failed

    def test_sync_with_sim_adopts_churn(self):
        pol = self._policy()
        sim = ClusterSim.heterogeneous(3, seed=2)
        pol.bind_sim(sim)
        sim.inject_failure(0)              # sim-side event the policy missed
        assert pol.sync_with_sim() == {0}
        assert pol.weights()[0] == 0.0
        sim.recover(0)
        assert pol.sync_with_sim() == set()

    def test_sync_without_sim_raises(self):
        with pytest.raises(RuntimeError, match="bind_sim"):
            self._policy().sync_with_sim()

    def test_hard_removal_reindexes_soft_failures(self):
        pol = self._policy(k=4)
        pol.fail(3, remove=False)
        pol.fail(1)                        # hard removal shifts indices down
        assert pol.failed == {2}
        assert len(pol.weights()) == 3


class TestResolveInflight:
    def test_failed_channel_gets_zero_share(self):
        b = _seeded_balancer()
        w = b.weights()
        shares = b.resolve_inflight(0.5 * w, failed=[2])
        assert shares[2] == 0.0
        assert abs(shares.sum() - 1.0) < 1e-6
        assert (shares[np.arange(4) != 2] > 0).all()
        # the steady-state cache is untouched by the mid-flight re-solve
        np.testing.assert_array_equal(b.weights(), w)

    def test_finished_job_solves_to_zero(self):
        b = _seeded_balancer()
        np.testing.assert_array_equal(
            b.resolve_inflight(np.full(4, 0.25)), np.zeros(4))

    def test_no_active_channels_solves_to_zero(self):
        b = _seeded_balancer()
        np.testing.assert_array_equal(
            b.resolve_inflight(np.zeros(4), failed=range(4)), np.zeros(4))

    def test_firm_solve_skips_pgd_and_returns_warm_start(self):
        b = _seeded_balancer(adaptive_refresh=True, refresh_target_rel=1e9)
        w = b.weights()                    # firm by construction of the gate
        assert b._last_rel_fragility is not None
        done = w * np.array([0.5, 0.2, 0.0, 0.1])
        expected = np.maximum(np.asarray(w, np.float64) - done, 0.0)
        expected /= expected.sum()
        np.testing.assert_allclose(b.resolve_inflight(done), expected,
                                   rtol=0, atol=1e-12)

    def test_failure_always_forces_the_solve(self):
        """Losing a channel is a model change, never absorbable drift: even
        a firm solve must re-run the PGD when a channel died."""
        b = _seeded_balancer(adaptive_refresh=True, refresh_target_rel=1e9)
        w = b.weights()
        done = 0.3 * w
        warm = np.maximum(np.asarray(w, np.float64) - done, 0.0)
        warm[1] = 0.0
        warm /= warm.sum()
        shares = b.resolve_inflight(done, failed=[1])
        assert shares[1] == 0.0
        assert not np.array_equal(shares, warm)   # PGD moved off the warm start

    def test_workflow_resolve_inflight_masks_failed(self):
        dag = _dag()
        wb = _seeded_workflow_balancer(dag)
        wb.handle_failure("a", 0)
        out = wb.resolve_inflight({"a": np.full(3, 0.2)})
        assert out["a"][0] == 0.0
        assert abs(out["a"].sum() - 1.0) < 1e-6
        assert abs(out["b"].sum() - 1.0) < 1e-6
        wb.handle_recovery("a", 0)
        assert wb.failed_channels() == {}
        assert wb.weights()["a"][0] > 0.0

    def test_workflow_failure_validates_stage(self):
        wb = _seeded_workflow_balancer(_dag())
        with pytest.raises(KeyError):
            wb.handle_failure("nope", 0)


class TestCheckpointStore:
    def test_missing_leaf_names_the_key(self, tmp_path):
        save(str(tmp_path), 1, {"a": np.zeros(3), "b": np.ones((2, 2))})
        with pytest.raises(ValueError, match=r"leaf 'c' missing"):
            restore(str(tmp_path), {"a": np.zeros(3), "c": np.zeros(2)})

    def test_shape_mismatch_names_leaf_and_shapes(self, tmp_path):
        save(str(tmp_path), 1, {"a": np.zeros(3)})
        with pytest.raises(ValueError, match=r"'a'.*expected \(4,\).*found \(3,\)"):
            restore(str(tmp_path), {"a": np.zeros(4)})

    def test_latest_step_survives_pointer_damage(self, tmp_path):
        d = str(tmp_path)
        save(d, 1, {"x": np.zeros(2)})
        save(d, 2, {"x": np.zeros(2)})
        ptr = os.path.join(d, "LATEST")
        for damage in ("garbage", ""):
            with open(ptr, "w") as f:
                f.write(damage)
            assert latest_step(d) == 2
        os.remove(ptr)
        assert latest_step(d) == 2
        # an in-flight (incomplete) step dir is not a restore candidate
        os.makedirs(os.path.join(d, "step_00000009"))
        assert latest_step(d) == 2
        assert latest_step(str(tmp_path / "nowhere")) is None

    def test_restore_pipeline_requires_manifest(self, tmp_path):
        save(str(tmp_path), 1, {"x": np.zeros(2)})
        with pytest.raises(ValueError, match="pipeline"):
            restore_pipeline(str(tmp_path))

    def test_autotune_cache_rides_the_manifest(self, tmp_path):
        key = autotune._key(8, 3, 64, "xla", False, "defective")
        autotune.clear_cache()
        try:
            autotune._CACHE[key] = {"block_f": 4, "source": "sweep"}
            save_pipeline(str(tmp_path), 1, _seeded_balancer(k=3, seed=1))
            autotune.clear_cache()
            assert key not in autotune.cache_state()
            restore_pipeline(str(tmp_path))
            assert autotune.cache_state()[key]["block_f"] == 4
        finally:
            autotune.clear_cache()

    def test_manifest_carries_inflight_and_model_tree(self, tmp_path):
        b = _seeded_balancer()
        tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
        save_pipeline(str(tmp_path), 5, b,
                      inflight={"done": [0.1, 0.2, 0.0, 0.0]},
                      tree=tree, meta={"note": "mid-flight"})
        b2, inflight, meta = restore_pipeline(
            str(tmp_path), template={"w": np.zeros((2, 3), np.float32)})
        assert inflight == {"done": [0.1, 0.2, 0.0, 0.0]}
        assert meta["note"] == "mid-flight"
        np.testing.assert_array_equal(meta["tree"]["w"], tree["w"])
        np.testing.assert_array_equal(b.weights(), b2.weights())

    def test_manager_maybe_save_pipeline(self, tmp_path):
        b = _seeded_balancer()
        mgr = CheckpointManager(str(tmp_path), interval=2, keep=2)
        saved = [s for s in range(1, 7)
                 if mgr.maybe_save_pipeline(s, b, blocking=True)]
        assert saved == [2, 4, 6]
        assert latest_step(str(tmp_path)) == 6
        kept = [p for p in os.listdir(str(tmp_path)) if p.startswith("step_")]
        assert len(kept) == 2              # bounded retention
        b2, _, _ = restore_pipeline(str(tmp_path))
        np.testing.assert_array_equal(b.weights(), b2.weights())
