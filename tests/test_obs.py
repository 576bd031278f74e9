"""Observability tier: tracer mechanics, exporters, StreamingStat.merge,
and the zero-perturbation contract (docs/OBSERVABILITY.md).

Acceptance anchors:
  * off-by-default no-op path — with tracing off, ``span`` hands back a
    shared no-op and nothing is recorded;
  * ring-buffer bounds + drop accounting, name-registry rejection at emit
    time (RPA090's runtime half), tick correlation;
  * exporters round-trip: JSONL read/write, schema validation, Perfetto
    ``trace_event`` structure, phase totals;
  * a recorded span is also a profiler annotation labelled ``name:attr``;
    the off path and a non-recording ``timed_span`` never touch the
    profiler;
  * ``StreamingStat.merge`` equals the concatenated stream on the exact
    moment fields and stays a uniform reservoir on quantiles;
  * ``solve_dag``'s device waits nest in their phases, its
    ``profile["launches"]`` matches a hand count, and it reads the device
    back only in its two counted ``jax.device_get`` calls;
  * zero perturbation — the serving engine and the chaos kill/restore
    harness produce bitwise-identical results traced vs untraced, and a
    restored replica's trace carries the restore event with the manifest
    step (the ``fault``-marked tests ride ci.sh's chaos tier).
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import events as obs_events
from repro.obs import export as obs_export
from repro.obs import names as obs_names
from repro.obs import trace as obs
from repro.obs.trace import _NOOP, Tracer
from repro.serve.telemetry import StreamingStat
from repro.workflow.dag import Stage, StageDAG, linear_edges


@pytest.fixture
def tracing():
    """Force-enable the module tracer for one test; restore and clear."""
    prev = obs.enabled()
    obs.clear()
    obs.set_enabled(True)
    yield
    obs.set_enabled(prev)
    obs.set_tick(None)
    obs.clear()


def _two_width_dag(seed=7):
    """Three stages in series, 3, 5 and 3 channels wide."""
    rng = np.random.default_rng(seed)
    stages = [Stage(n, rng.uniform(10, 30, k), rng.uniform(1, 4, k))
              for n, k in (("a", 3), ("b", 5), ("c", 3))]
    return StageDAG(stages, linear_edges(["a", "b", "c"]))


def _fixed_stage_dag(seed=7):
    """Three stages in series, 3, 1 and 5 channels wide."""
    rng = np.random.default_rng(seed)
    stages = [Stage(n, rng.uniform(10, 30, k), rng.uniform(1, 4, k))
              for n, k in (("a", 3), ("b", 1), ("c", 5))]
    return StageDAG(stages, linear_edges(["a", "b", "c"]))


def _dag(k=3, seed=7):
    rng = np.random.default_rng(seed)
    stages = [Stage("a", rng.uniform(10, 30, k), rng.uniform(1, 4, k)),
              Stage("b", rng.uniform(10, 30, k), rng.uniform(1, 4, k))]
    return StageDAG(stages, linear_edges(["a", "b"]))


# ---------------------------------------------------------------------------
# tracer mechanics
# ---------------------------------------------------------------------------
class TestTracer:
    def test_off_by_default_is_noop(self):
        assert not obs.enabled()  # REPRO_TRACE unset in the test env
        sp = obs.span(obs_names.SPAN_SIM_STEP, sim="x")
        assert sp is _NOOP
        with sp:
            pass
        obs.event(obs_names.EV_CHURN, kind="fail")
        obs_events.churn("fail", 0, "test")
        assert obs.records() == []

    def test_timed_span_measures_even_when_off(self):
        assert not obs.enabled()
        with obs.timed_span(obs_names.SPAN_SOLVER_PHASE, phase="p") as sp:
            sum(range(1000))
        assert sp.dur_us > 0.0       # the hand-timer replacement contract
        assert obs.records() == []   # ...but nothing was recorded

    def test_span_records_fields(self, tracing):
        with obs.span(obs_names.SPAN_SIM_STEP, sim="cluster", k=4):
            pass
        (rec,) = obs.records()
        assert rec["type"] == "span"
        assert rec["name"] == obs_names.SPAN_SIM_STEP
        assert rec["dur_us"] >= 0.0
        assert rec["attrs"] == {"sim": "cluster", "k": 4}
        assert isinstance(rec["seq"], int)

    def test_event_and_tick_correlation(self, tracing):
        obs.set_tick(7)
        obs_events.dirty("engine", 3, "drift", 0.125)
        (rec,) = obs.records()
        assert rec["type"] == "event" and rec["tick"] == 7
        assert rec["attrs"] == {"scope": "engine", "key": "3",
                                "cause": "drift", "drift": 0.125}
        assert obs.current_tick() == 7

    def test_unregistered_name_rejected_at_emit(self, tracing):
        with pytest.raises(ValueError, match="unregistered trace name"):
            obs.event("made.up.name", x=1)
        with pytest.raises(ValueError, match="RPA090"):
            with obs.span("also.not.registered"):
                pass

    def test_ring_buffer_drops_oldest_and_counts(self):
        t = Tracer(capacity=8)
        t.set_enabled(True)
        for i in range(20):
            t.event(obs_names.EV_CHURN, i=i)
        recs = t.records()
        assert len(recs) == 8
        assert [r["attrs"]["i"] for r in recs] == list(range(12, 20))
        assert [r["attrs"]["i"] for r in t.records(since=recs[-3]["seq"])] \
            == [18, 19]
        assert t.records(since=recs[-1]["seq"]) == []
        assert t.dropped() == 12
        t.clear()
        assert t.records() == [] and t.dropped() == 0

    def test_capture_scopes_records_and_restores_state(self, tracing):
        obs.set_enabled(False)
        obs_events.churn("fail", 0, "before")  # off: not recorded
        with obs.capture() as cap:
            assert obs.enabled()
            obs_events.churn("recover", 1, "inside")
        assert not obs.enabled()               # restored to pre-capture
        assert [r["attrs"]["source"] for r in cap] == ["inside"]

    def test_traced_decorator(self, tracing):
        @obs.traced(obs_names.SPAN_SIM_STEP, sim="deco")
        def f(x):
            return x + 1

        assert f(1) == 2
        (rec,) = obs.records()
        assert rec["attrs"] == {"sim": "deco"}
        obs.set_enabled(False)
        obs.clear()
        assert f(2) == 3 and obs.records() == []


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
def _sample_records(tick=3):
    obs.set_tick(tick)
    with obs.span(obs_names.SPAN_SOLVER_PHASE, phase="presolve"):
        pass
    with obs.span(obs_names.SPAN_SOLVER_PHASE, phase="refine"):
        pass
    obs_events.fragility_gate(True, 0.02, 0.1)
    obs_events.ckpt_save(5, "engine", "/tmp/ck")
    return obs.records()


class TestExport:
    def test_jsonl_round_trip(self, tracing, tmp_path):
        recs = _sample_records()
        path = str(tmp_path / "t.jsonl")
        assert obs_export.write_jsonl(recs, path) == len(recs)
        back = obs_export.read_jsonl(path)
        assert back == json.loads(json.dumps(recs))  # same after JSON trip

    def test_validate_accepts_real_records(self, tracing):
        recs = _sample_records()
        assert obs_export.validate_records(recs) == len(recs)
        assert obs_export.span_kinds(recs) == {obs_names.SPAN_SOLVER_PHASE}
        assert obs_export.event_types(recs) == {obs_names.EV_FRAGILITY,
                                                obs_names.EV_CKPT_SAVE}

    def test_validate_rejects_malformed(self, tracing):
        (good,) = [r for r in _sample_records()
                   if r["name"] == obs_names.EV_CKPT_SAVE]

        def bad(**patch):
            return [{**good, **patch}]

        with pytest.raises(ValueError, match="registry"):
            obs_export.validate_records(bad(name="rogue.name"))
        with pytest.raises(ValueError, match="event with a span name"):
            obs_export.validate_records(bad(name=obs_names.SPAN_SIM_STEP))
        with pytest.raises(ValueError, match="bad type"):
            obs_export.validate_records(bad(type="metric"))
        with pytest.raises(ValueError, match="dur_us"):
            obs_export.validate_records(
                bad(type="span", name=obs_names.SPAN_SIM_STEP, dur_us=-1.0))
        with pytest.raises(ValueError, match="attrs"):
            obs_export.validate_records(bad(attrs=None))
        with pytest.raises(ValueError, match="ts_us"):
            obs_export.validate_records(bad(ts_us=None))

    def test_perfetto_structure(self, tracing):
        doc = obs_export.to_perfetto(_sample_records(tick=9))
        json.dumps(doc)  # loadable
        evs = doc["traceEvents"]
        assert evs[0]["ph"] == "M" and evs[0]["name"] == "process_name"
        xs = [e for e in evs if e["ph"] == "X"]
        inst = [e for e in evs if e["ph"] == "i"]
        assert len(xs) == 2 and all(e["dur"] >= 0 for e in xs)
        assert len(inst) == 2 and all(e["s"] == "p" for e in inst)
        assert all(e["args"]["tick"] == 9 for e in xs + inst)
        assert {e["tid"] for e in xs} == {0}  # remapped to small ints

    def test_phase_totals(self, tracing):
        totals = obs_export.phase_totals(_sample_records())
        assert set(totals) == {"presolve", "refine"}
        assert all(v >= 0 for v in totals.values())


# ---------------------------------------------------------------------------
# profiler annotations: recorded spans share the device trace's clock
# ---------------------------------------------------------------------------
class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; logs its use."""

    log = []

    def __init__(self, text):
        self.text = text
        self.log.append(("new", text))

    def __enter__(self):
        self.log.append(("enter", self.text))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.text))
        return False


@pytest.fixture
def fake_annotation(monkeypatch):
    import jax.profiler

    _FakeAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    return _FakeAnnotation.log


class TestProfilerAnnotations:
    def test_recorded_span_opens_and_closes_its_annotation(
            self, tracing, fake_annotation):
        with obs.span(obs_names.SPAN_SOLVER_PHASE, phase="presolve"):
            assert fake_annotation == [("new", "solver.phase:presolve"),
                                       ("enter", "solver.phase:presolve")]
        assert fake_annotation[-1] == ("exit", "solver.phase:presolve")
        with obs.timed_span(obs_names.SPAN_SOLVER_WAIT, phase="refine"):
            pass
        assert fake_annotation[3:] == [("new", "solver.wait:refine"),
                                       ("enter", "solver.wait:refine"),
                                       ("exit", "solver.wait:refine")]
        assert len(obs.records()) == 2

    def test_off_path_and_unrecorded_timed_span_skip_the_profiler(
            self, fake_annotation):
        assert not obs.enabled()
        with obs.span(obs_names.SPAN_SOLVER_PHASE, phase="presolve"):
            pass
        with obs.timed_span(obs_names.SPAN_SOLVER_PHASE, phase="p") as sp:
            pass
        assert sp.dur_us >= 0.0
        assert fake_annotation == []

    def test_recorded_spans_land_in_the_profiler_trace(self, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData
        from repro.workflow import solve_dag

        dag = _two_width_dag()
        solve_dag(dag, steps=6, restarts=1, num_t=64)    # compile first
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.capture() as cap:
                solve_dag(dag, steps=6, restarts=1, num_t=64)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        pd = ProfileData.from_file(path)
        host = sorted(ev.name for plane in pd.planes
                      if plane.name.startswith("/host:")
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith("solver."))
        spans = sorted(obs.label(r["name"], r["attrs"]) for r in cap
                       if r["type"] == "span")
        assert host == spans
        assert "solver.wait:presolve" in host

    @pytest.mark.parametrize("attrs, want", [
        ({"phase": "triage"}, "solver.phase:triage"),
        ({"stage": "commit", "phase": "x"}, "solver.phase:commit"),
        ({"mode": "grad", "F": 8}, "solver.phase:grad"),
        ({"F": 8}, "solver.phase"),
    ])
    def test_label_names_the_part(self, attrs, want):
        assert obs.label(obs_names.SPAN_SOLVER_PHASE, attrs) == want


# ---------------------------------------------------------------------------
# StreamingStat.merge (weighted Welford + reservoir subsample)
# ---------------------------------------------------------------------------
class TestStreamingStatMerge:
    def test_moments_match_concatenated_stream(self):
        rng = np.random.default_rng(0)
        a = rng.normal(5.0, 2.0, 700)
        b = rng.lognormal(1.0, 0.5, 400)
        s1, s2, ground = (StreamingStat(capacity=64) for _ in range(3))
        for x in a:
            s1.add(x)
            ground.add(x)
        for x in b:
            s2.add(x)
            ground.add(x)
        s1.merge(s2)
        assert s1.count == ground.count == 1100
        assert np.isclose(s1.mean(), ground.mean(), rtol=1e-12)
        assert np.isclose(s1.var(), ground.var(), rtol=1e-9)
        assert s1.max() == ground.max() and s1.min() == ground.min()

    def test_reservoir_quantiles_track_concatenated(self):
        rng = np.random.default_rng(1)
        a = rng.uniform(0.0, 1.0, 3000)
        b = rng.uniform(0.0, 2.0, 1000)
        s1 = StreamingStat(capacity=512, seed=3)
        s2 = StreamingStat(capacity=512, seed=4)
        for x in a:
            s1.add(x)
        for x in b:
            s2.add(x)
        s1.merge(s2)
        concat = np.concatenate([a, b])
        for q in (0.25, 0.5, 0.9):
            assert abs(s1.quantile(q) - np.quantile(concat, q)) < 0.15, q
        assert len(s1._res) == 512  # bounded memory survived the merge

    def test_merge_empty_cases(self):
        s1, s2 = StreamingStat(), StreamingStat()
        for x in (1.0, 2.0, 3.0):
            s2.add(x)
        s1.merge(s2)  # into empty: adopt
        assert s1.count == 3 and s1.mean() == 2.0
        s3 = StreamingStat()
        s1.merge(s3)  # empty other: no-op
        assert s1.count == 3 and s1.mean() == 2.0

    def test_merge_capacity_mismatch_raises(self):
        with pytest.raises(ValueError, match="capacities differ"):
            StreamingStat(capacity=8).merge(StreamingStat(capacity=16))

    def test_merge_is_deterministic(self):
        rng = np.random.default_rng(2)
        xs, ys = rng.uniform(0, 1, 300), rng.uniform(1, 2, 300)

        def build():
            s1 = StreamingStat(capacity=128, seed=11)
            s2 = StreamingStat(capacity=128, seed=12)
            for x in xs:
                s1.add(x)
            for y in ys:
                s2.add(y)
            return s1.merge(s2)

        assert build()._res == build()._res


# ---------------------------------------------------------------------------
# solver integration: spans are the single timing source
# ---------------------------------------------------------------------------
class TestSolverSpans:
    def test_solve_dag_phase_spans_match_profile(self):
        from repro.workflow import solve_dag

        with obs.capture() as cap:
            dec = solve_dag(_dag(), steps=6, restarts=1, num_t=64)
        totals = obs_export.phase_totals(cap)
        ladder = {"starts", "presolve", "triage", "refine", "final_score"}
        assert ladder <= set(totals), totals
        # the decision's profile reads the SAME spans
        assert ladder <= set(dec.profile["phase_us"]), dec.profile
        # solve_dag's ops calls run inside jit, so the kernel tier shows
        # up as compile audit events or not at all (warm cache) — never
        # as in-jit spans (the zero-perturbation jit-boundary rule); the
        # solver's own blocks on the device are its wait spans
        assert obs_export.span_kinds(cap) == {obs_names.SPAN_SOLVER_PHASE,
                                              obs_names.SPAN_SOLVER_WAIT}
        obs_export.validate_records(cap)

    @staticmethod
    def _posteriors(dag):
        from repro.core.bayes import nig_init, nig_update_batch

        rng = np.random.default_rng(0)
        out = {}
        for s in dag.stages:
            nig = nig_init(s.k, m0=float(np.mean(s.mus)))
            rates = rng.normal(s.mus, s.sigmas).astype(np.float32)
            out[s.name] = nig_update_batch(nig, jnp.asarray(rates),
                                           jnp.ones(s.k, jnp.float32))
        return out

    @pytest.mark.parametrize("risk", [False, True], ids=["plain", "risk"])
    def test_waits_lie_inside_their_phase(self, risk):
        from repro.workflow import solve_dag

        dag = _two_width_dag()
        kw = (dict(risk_lam=0.5, posteriors=self._posteriors(dag))
              if risk else {})
        with obs.capture() as cap:
            solve_dag(dag, steps=6, restarts=1, num_t=64, **kw)
        spans = [r for r in cap if r["type"] == "span"]
        phases = {r["attrs"]["phase"]: r for r in spans
                  if r["name"] == obs_names.SPAN_SOLVER_PHASE}
        waits = [r for r in spans if r["name"] == obs_names.SPAN_SOLVER_WAIT]
        for w in waits:
            ph = phases[w["attrs"]["phase"]]
            assert ph["ts_us"] <= w["ts_us"]
            assert w["ts_us"] + w["dur_us"] <= ph["ts_us"] + ph["dur_us"]
        # one wait ends each rung, the final score's reading the whole
        # pool back; with posteriors the fragility's one family group then
        # reads its cotangents, its rows and its launch's gradients
        want = ["presolve", "triage", "refine", "final_score"] + (
            ["fragility"] * 3 if risk else [])
        assert [w["attrs"]["phase"] for w in waits] == want
        # the phases are contiguous laps: their sum is the whole solve
        order = sorted(phases.values(), key=lambda r: r["ts_us"])
        for a, b in zip(order, order[1:]):
            assert a["ts_us"] + a["dur_us"] <= b["ts_us"]

    def test_launches_match_a_hand_count(self):
        from repro.workflow import solve_dag

        dag = _two_width_dag()       # widths 3, 5, 3: 11 real channels
        dec = solve_dag(dag, steps=6, restarts=1, num_t=64, block_f=4)
        p = dec.profile
        R, surv, ncand = p["starts"], p["survivors"], p["pool"]
        assert R == 3 and ncand == 3 * surv

        def entry(phase, mode, n, launches, num_t):
            rows = 3 * n
            bf = min(4, rows)
            return {"phase": phase, "mode": mode, "family": "normal",
                    "launches": launches, "rows": rows,
                    "rows_padded": -(-rows // bf) * bf, "block_f": bf,
                    "k": 5, "num_t": num_t, "channels": 11 * n,
                    # the XLA path packs nothing: one 128-lane group a block
                    "pack": 1, "lanes": -(-rows // bf) * 128}

        assert p["launches"] == [
            entry("presolve", "grad", R, p["presolve_steps_run"], 64),
            entry("triage", "fwd", 2 * R, 1, 64),
            entry("refine", "grad", surv, p["refine_steps_run"], 64),
            entry("final_score", "fwd", ncand, 1, 2048)]
        # 3 starts x 3 stages = 9 rows in blocks of 4: 12 padded rows
        assert p["launches"][0]["rows_padded"] == 12
        assert p["presolve_steps_run"] == 6

    def test_launches_match_a_hand_count_with_a_fixed_stage(self):
        """A single-channel stage is no PGD row: each phase books one
        launch of its one-channel row a candidate, and the loop's launches
        hold the other two stages; triage and the final score every
        stage."""
        from repro.workflow import solve_dag

        dag = _fixed_stage_dag()     # widths 3, 1, 5: 9 real channels
        dec = solve_dag(dag, steps=6, restarts=1, num_t=64, block_f=4)
        p = dec.profile
        R, surv, ncand = p["starts"], p["survivors"], p["pool"]
        assert R == 3 and ncand == 3 * surv and p["fixed_stages"] == 1

        def entry(phase, mode, n, launches, num_t, stages, k, channels):
            rows = stages * n
            bf = min(4, rows)
            return {"phase": phase, "mode": mode, "family": "normal",
                    "launches": launches, "rows": rows,
                    "rows_padded": -(-rows // bf) * bf, "block_f": bf,
                    "k": k, "num_t": num_t, "channels": channels * n,
                    "pack": 1, "lanes": -(-rows // bf) * 128}

        assert p["launches"] == [
            entry("presolve", "grad", R, 1, 64, 1, 1, 1),
            entry("presolve", "grad", R, p["presolve_steps_run"], 64, 2, 5,
                  8),
            entry("triage", "fwd", 2 * R, 1, 64, 3, 5, 9),
            entry("refine", "grad", surv, 1, 64, 1, 1, 1),
            entry("refine", "grad", surv, p["refine_steps_run"], 64, 2, 5,
                  8),
            entry("final_score", "fwd", ncand, 1, 2048, 3, 5, 9)]
        # 3 starts x 2 movable stages = 6 rows in blocks of 4: 8 padded
        assert p["launches"][1]["rows_padded"] == 8
        assert p["presolve_steps_run"] == 6

    @pytest.mark.parametrize("risk", [False, True], ids=["plain", "risk"])
    def test_one_readback_a_wait(self, risk):
        from repro.workflow import solve_dag

        dag = _two_width_dag()
        kw = (dict(risk_lam=0.5, posteriors=self._posteriors(dag))
              if risk else {})
        dec = solve_dag(dag, steps=6, restarts=1, num_t=64, **kw)
        # triage and the final score; the fragility's reads are its own
        assert dec.profile["readbacks"] == 2

    def test_every_read_is_an_explicit_transfer(self, monkeypatch):
        """A plain solve reads the device only through ``jax.device_get``.

        The transfer guard refuses implicit reads where the backend copies
        to the host. The CPU backend lends its buffers to numpy without a
        copy and never trips it, so two hooks stand in for it there: one on
        ``ArrayImpl._value`` (``float``, ``int``, ``__array__``) outside an
        explicit ``device_get``, one on the solver's ``np.asarray`` and
        ``np.array`` of a device array."""
        import jax
        from jax._src import array as jax_array
        from jax._src.lib import guard_lib

        import repro.workflow.solve as solve_mod

        implicit = []
        value = jax_array.ArrayImpl._value

        def guarded(self):
            if not guard_lib.thread_local_state().explicit_device_get:
                implicit.append(("value", self.shape))
            return value.fget(self)

        class _Numpy:
            def __getattr__(self, name):
                return getattr(np, name)

            def _watch(self, fn, a, *args, **kw):
                if isinstance(a, jax.Array):
                    implicit.append((fn.__name__, a.shape))
                return fn(a, *args, **kw)

            def asarray(self, a, *args, **kw):
                return self._watch(np.asarray, a, *args, **kw)

            def array(self, a, *args, **kw):
                return self._watch(np.array, a, *args, **kw)

        dag = _two_width_dag()
        monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(guarded))
        monkeypatch.setattr(solve_mod, "np", _Numpy())
        with jax.transfer_guard_device_to_host("disallow"):
            dec = solve_mod.solve_dag(dag, steps=6, restarts=1, num_t=64)
            assert implicit == []
            # the hooks see what the guard would refuse
            float(jnp.float32(1.0) + 1.0)
            solve_mod.np.asarray(jnp.zeros(2))
        assert implicit == [("value", ()), ("asarray", (2,))]
        assert dec.profile["readbacks"] == 2

    def test_decision_bitwise_traced_vs_untraced(self):
        from repro.workflow import solve_dag

        dag = _two_width_dag()
        plain = solve_dag(dag, steps=6, restarts=1, num_t=64)
        with obs.capture() as cap:
            traced = solve_dag(dag, steps=6, restarts=1, num_t=64)
        assert cap
        assert plain.weights.keys() == traced.weights.keys()
        for name in plain.weights:
            np.testing.assert_array_equal(plain.weights[name],
                                          traced.weights[name])
        assert plain.makespan_mu == traced.makespan_mu
        assert plain.makespan_var == traced.makespan_var
        np.testing.assert_array_equal(plain.stage_mu, traced.stage_mu)
        np.testing.assert_array_equal(plain.stage_var, traced.stage_var)
        assert plain.profile["launches"] == traced.profile["launches"]

    def test_kernel_launch_span_attrs(self):
        from repro.kernels import ops

        W = np.full((2, 3), 1 / 3, np.float32)
        mus = np.linspace(10, 20, 6).reshape(2, 3).astype(np.float32)
        sigmas = np.full((2, 3), 1.5, np.float32)
        with obs.capture() as cap:
            ops.frontier_moments(W, mus, sigmas, num_t=32)
        launches = [r for r in cap
                    if r["name"] == obs_names.SPAN_KERNEL_LAUNCH]
        assert launches, cap
        at = launches[0]["attrs"]
        assert at["mode"] == "fwd" and at["F"] == 2 and at["K"] == 3
        assert at["autotune"] in ("hit", "miss", "explicit", "none")


# ---------------------------------------------------------------------------
# zero perturbation: bitwise-identical behavior traced vs untraced
# ---------------------------------------------------------------------------
def _engine_run(ticks=5, seed=0):
    from repro.serve.engine import WorkflowEngine

    templates = {"wf": _dag(k=2, seed=3)}
    eng = WorkflowEngine(templates, max_live=8, lam_var=0.02, num_t=64,
                        seed=seed, prior_obs=2, settle_steps=2)
    rng = np.random.default_rng(seed)
    outs = []
    for _ in range(ticks):
        arrivals = [("wf", 30.0)] * int(rng.poisson(2.0))
        out = eng.tick(arrivals)
        outs.append((out["live"], out["queue"], out["rows"],
                     out["launches"],
                     tuple(round(r["join_latency_s"], 12)
                           for r in out["retired"])))
    return outs


@pytest.mark.fault
class TestZeroPerturbation:
    def test_engine_ticks_bitwise_traced_vs_untraced(self, tracing):
        obs.set_enabled(False)
        plain = _engine_run()
        obs.set_enabled(True)
        obs.clear()
        traced = _engine_run()
        assert plain == traced
        assert obs.records(), "traced run recorded nothing"

    def test_chaos_parity_holds_with_tracing(self, tracing):
        from repro.sim.chaos import run_chaos_trace

        obs.set_enabled(False)
        res_plain = run_chaos_trace(num_channels=4, ticks=6, kill_every=3)
        obs.set_enabled(True)
        obs.clear()
        res = run_chaos_trace(num_channels=4, ticks=6, kill_every=3)
        # parity verified continuously INSIDE the traced run...
        assert res.kills == 1 and res.parity_checks == 1
        # ...and the traced trajectory is bitwise the untraced one
        np.testing.assert_array_equal(res.joins, res_plain.joins)
        recs = obs.records()
        obs_export.validate_records(recs)
        restores = [r for r in recs
                    if r["name"] == obs_names.EV_CKPT_RESTORE]
        assert [(r["attrs"]["step"], r["attrs"]["kind"])
                for r in restores] == [(3, "balancer")]
        assert obs_names.SPAN_CHAOS_CYCLE in obs_export.span_kinds(recs)

    def test_workflow_chaos_restore_event_carries_manifest_step(
            self, tracing):
        from repro.sim.chaos import run_workflow_chaos_trace

        res = run_workflow_chaos_trace(_dag(), ticks=4, kill_every=2)
        assert res.kills == 1 and res.parity_checks == 1
        restores = [r for r in obs.records()
                    if r["name"] == obs_names.EV_CKPT_RESTORE]
        assert [(r["attrs"]["step"], r["attrs"]["kind"])
                for r in restores] == [(2, "workflow")]

    def test_trace_state_not_checkpointed(self, tracing, tmp_path):
        from repro.ckpt import save_pipeline
        from repro.sched import UncertaintyAwareBalancer

        bal = UncertaintyAwareBalancer(num_channels=3, lam=0.05,
                                       explore=0.0)
        rng = np.random.default_rng(0)
        for _ in range(3):
            bal.observe(rng.uniform(8, 30, 3), np.full(3, 1 / 3))
        with obs.span(obs_names.SPAN_SCHED_REFRESH, kind="fleet"):
            bal.weights()
        path = save_pipeline(str(tmp_path), 1, bal)
        with open(f"{path}/meta.json") as f:
            manifest = f.read()
        # no trace/span/obs state rides the manifest — a restored replica
        # starts a FRESH trace whose first record is the restore event
        assert "trace" not in manifest and "span" not in manifest
