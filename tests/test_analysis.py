"""The repro.analysis tier: lint framework + every rule (bad fixture fires,
good fixture stays silent), the CLI, pragma suppression, the self-clean
gate on the real source tree, and the REPRO_SANITIZE runtime sanitizer."""
import json
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.analysis import format_json, format_text, run_paths, rule_codes
from repro.analysis import sanitize as san


def _lint(tmp_path, source, select=None, name="fx.py"):
    """Write one fixture module and lint it; returns the findings."""
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return run_paths([str(p)], select=select)


def _codes(findings):
    return sorted(f.code for f in findings)


# ---------------------------------------------------------------------------
# framework mechanics
# ---------------------------------------------------------------------------
class TestFramework:
    def test_unparseable_file_is_a_finding(self, tmp_path):
        fs = _lint(tmp_path, "def broken(:\n")
        assert _codes(fs) == ["RPA000"]

    def test_every_rule_declares_unique_codes(self):
        codes = rule_codes()
        assert len(codes) >= 13  # the PR 6 rule set
        assert all(c.startswith("RPA") for c in codes)

    def test_findings_sort_and_format(self, tmp_path):
        fs = _lint(tmp_path, """
            def f(mus, sigmas):
                return mus + sigmas
            """)
        assert fs == sorted(fs)
        line = fs[0].format()
        assert "RPA001" in line and str(fs[0].line) in line

    def test_json_reporter_round_trips(self, tmp_path):
        fs = _lint(tmp_path, """
            def f(mus, sigmas):
                return mus
            """)
        data = json.loads(format_json(fs))
        assert data["count"] == len(fs)
        assert data["findings"][0]["code"] == "RPA001"
        assert "RPA001" in format_text(fs)

    def test_pragma_on_line_suppresses(self, tmp_path):
        fs = _lint(tmp_path, """
            def f(mus, sigmas):  # repro: allow[RPA001] fixture
                return mus
            """)
        assert fs == []

    def test_pragma_block_above_suppresses(self, tmp_path):
        fs = _lint(tmp_path, """
            # this helper is family-agnostic by design
            # repro: allow[RPA001] fixture justification
            def f(mus, sigmas):
                return mus
            """)
        assert fs == []

    def test_pragma_only_silences_named_code(self, tmp_path):
        fs = _lint(tmp_path, """
            def f(mus, sigmas):  # repro: allow[RPA050] wrong code
                return mus
            """)
        assert _codes(fs) == ["RPA001"]

    def test_select_filters(self, tmp_path):
        fs = _lint(tmp_path, """
            def f(mus, sigmas):
                return mus
            """, select=["RPA050"])
        assert fs == []

    def test_cli_exit_codes_and_json(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("def f(mus, sigmas):\n    return mus\n")
        root = pathlib.Path(__file__).resolve().parents[1]
        env_src = str(root / "src")
        r = subprocess.run(
            [sys.executable, "-m", "repro.analysis", str(bad), "--json"],
            capture_output=True, text=True, env={"PYTHONPATH": env_src,
                                                 "PATH": "/usr/bin:/bin"})
        assert r.returncode == 1
        assert json.loads(r.stdout)["findings"][0]["code"] == "RPA001"
        good = tmp_path / "good.py"
        good.write_text("x = 1\n")
        r = subprocess.run(
            [sys.executable, "-m", "repro.analysis", str(good)],
            capture_output=True, text=True, env={"PYTHONPATH": env_src,
                                                 "PATH": "/usr/bin:/bin"})
        assert r.returncode == 0


# ---------------------------------------------------------------------------
# per-rule: bad fixture fires, good fixture silent
# ---------------------------------------------------------------------------
class TestFamilyThreading:
    def test_rpa001_fires(self, tmp_path):
        fs = _lint(tmp_path, """
            def score(mus, sigmas, lam):
                return mus + lam * sigmas
            """)
        assert "RPA001" in _codes(fs)

    def test_rpa001_silent_with_family(self, tmp_path):
        fs = _lint(tmp_path, """
            def score(mus, sigmas, lam, family="normal"):
                return mus + lam * sigmas

            def score2(mus, sigmas, dist_id="normal"):
                return mus
            """)
        assert fs == []

    def test_rpa002_fires_on_dropped_family(self, tmp_path):
        fs = _lint(tmp_path, """
            def inner(mus, sigmas, family="normal"):
                return mus

            def outer(mus, sigmas, family="normal"):
                return inner(mus, sigmas)
            """)
        assert "RPA002" in _codes(fs)

    def test_rpa002_silent_when_forwarded(self, tmp_path):
        fs = _lint(tmp_path, """
            def inner(mus, sigmas, family="normal"):
                return mus

            def outer(mus, sigmas, family="normal"):
                return inner(mus, sigmas, family=family)
            """)
        assert fs == []


_VJP_GOOD = """
    import jax
    import functools

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def prim(x, y, n):
        return x * y

    def prim_fwd(x, y, n):
        return x * y, (x, y)

    def prim_bwd(n, res, ct):
        '''Zero y-cotangent is deliberate: y is a stop-gradient input.'''
        x, y = res
        return ct * y, ct * x

    prim.defvjp(prim_fwd, prim_bwd)
    """


class TestCustomVjpContract:
    def test_rpa010_fires_without_defvjp(self, tmp_path):
        fs = _lint(tmp_path, """
            import jax

            @jax.custom_vjp
            def prim(x, y):
                return x * y
            """)
        assert "RPA010" in _codes(fs)

    def test_rpa011_fires_on_cotangent_arity(self, tmp_path):
        fs = _lint(tmp_path, """
            import jax
            import functools

            @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
            def prim(x, y, n):
                return x * y

            def prim_fwd(x, y, n):
                return x * y, (x, y)

            def prim_bwd(n, res, ct):
                x, y = res
                return (ct * y,)

            prim.defvjp(prim_fwd, prim_bwd)
            """)
        assert "RPA011" in _codes(fs)

    def test_rpa012_fires_on_residual_mismatch(self, tmp_path):
        fs = _lint(tmp_path, """
            import jax

            @jax.custom_vjp
            def prim(x, y):
                return x * y

            def prim_fwd(x, y):
                return x * y, (x, y, x + y)

            def prim_bwd(res, ct):
                x, y = res
                return ct * y, ct * x

            prim.defvjp(prim_fwd, prim_bwd)
            """)
        assert "RPA012" in _codes(fs)

    def test_good_vjp_silent(self, tmp_path):
        assert _lint(tmp_path, _VJP_GOOD) == []


class TestStaticArgs:
    def test_rpa020_fires_on_traced_branch(self, tmp_path):
        fs = _lint(tmp_path, """
            import jax
            import functools

            @functools.partial(jax.jit, static_argnames=("n",))
            def f(x, n, mode):
                if mode:
                    return x * n
                return x
            """)
        assert "RPA020" in _codes(fs)

    def test_rpa021_fires_on_self_mutation(self, tmp_path):
        fs = _lint(tmp_path, """
            import jax

            class A:
                @jax.jit
                def f(self, x):
                    self.cache = x
                    return x
            """)
        assert "RPA021" in _codes(fs)

    def test_rpa022_fires_on_stale_static_name(self, tmp_path):
        fs = _lint(tmp_path, """
            import jax
            import functools

            @functools.partial(jax.jit, static_argnames=("gone",))
            def f(x, n):
                return x * n
            """)
        assert "RPA022" in _codes(fs)

    def test_good_static_usage_silent(self, tmp_path):
        fs = _lint(tmp_path, """
            import jax
            import functools

            @functools.partial(jax.jit, static_argnames=("mode", "n"))
            def f(x, n, mode):
                if mode:
                    return x * n
                return x
            """)
        assert fs == []


_PALLAS_WRAPPER = """
    import functools
    import jax
    from jax.experimental import pallas as pl

    def kernel(w_ref, out_ref):
        out_ref[...] = w_ref[...]

    def launch(W, num_t: int = 1024, block_f: int = {block_f}{extra_param}):
        F, K = W.shape
        {guard}
        return pl.pallas_call(
            kernel,
            grid=(F // block_f,),
            out_shape=jax.ShapeDtypeStruct((F,), W.dtype),
        )(W)
    """


class TestVmemAudit:
    def test_rpa030_fires_on_pgrad_overflow(self, tmp_path):
        # 256 overflows the VMEM budget for the empirical pgrad combo at the
        # K=1024/T=1024 audit point — the acceptance-criteria case
        src = _PALLAS_WRAPPER.format(
            block_f=256, extra_param=", param_grads: bool = False",
            guard="if F % block_f:\n            raise ValueError(F)")
        fs = _lint(tmp_path, src)
        assert "RPA030" in _codes(fs)
        msg = next(f for f in fs if f.code == "RPA030").message
        assert "pgrad" in msg and "128" in msg  # largest safe fused block

    def test_rpa030_silent_on_safe_fwd_default(self, tmp_path):
        src = _PALLAS_WRAPPER.format(
            block_f=128, extra_param="",
            guard="if F % block_f:\n            raise ValueError(F)")
        assert _lint(tmp_path, src) == []

    def test_rpa031_fires_without_divisibility_guard(self, tmp_path):
        src = _PALLAS_WRAPPER.format(block_f=128, extra_param="", guard="pass")
        fs = _lint(tmp_path, src)
        assert "RPA031" in _codes(fs)

    def test_real_defaults_match_the_budget_model(self):
        """The shipped kernel defaults must sit inside the same budget the
        lint rule audits: 128 fits every fwd combo, 64 every fused one."""
        from repro.core.distributions import FAMILIES
        from repro.kernels import autotune

        for dist_id in FAMILIES:
            assert autotune.vmem_bytes(128, 1024, 1024, fused=False,
                                       dist_id=dist_id) \
                <= autotune._VMEM_BUDGET_BYTES
            for params in (False, True):
                assert autotune.vmem_bytes(64, 1024, 1024, fused=True,
                                           dist_id=dist_id, params=params) \
                    <= autotune._VMEM_BUDGET_BYTES


class TestContracts:
    def test_rpa040_fires_on_undocumented_zero_cotangent(self, tmp_path):
        fs = _lint(tmp_path, """
            import jax.numpy as jnp

            def prim_bwd(res, ct):
                x, y = res
                return ct * y, jnp.zeros_like(x)
            """)
        assert "RPA040" in _codes(fs)

    def test_rpa040_silent_when_documented(self, tmp_path):
        fs = _lint(tmp_path, """
            import jax.numpy as jnp

            def prim_bwd(res, ct):
                '''y gets a zero cotangent: it is a stop-gradient constant.'''
                x, y = res
                return ct * y, jnp.zeros_like(x)
            """)
        assert fs == []

    def test_rpa050_fires_on_every_spelling(self, tmp_path):
        fs = _lint(tmp_path, """
            import repro.core.normal
            from repro.core.normal import Phi
            from repro.core import normal
            """)
        assert _codes(fs).count("RPA050") == 3

    def test_rpa050_silent_on_distributions(self, tmp_path):
        fs = _lint(tmp_path, """
            from repro.core.distributions import Phi, safe_cdf
            """)
        assert fs == []


class TestFidelityKnob:
    def test_rpa070_fires_on_literal_num_t(self, tmp_path):
        fs = _lint(tmp_path, """
            from repro.kernels import ops

            def f(W, mus, sigmas, family):
                return ops.frontier_moments(W, mus, sigmas, num_t=2048,
                                            family=family)
            """, select=["RPA070"])
        assert _codes(fs) == ["RPA070"]

    def test_rpa070_fires_on_constant_arithmetic(self, tmp_path):
        fs = _lint(tmp_path, """
            from repro.kernels import ops

            def f(W, mus, sigmas, family):
                return ops.frontier_moments_with_grads(
                    W, mus, sigmas, num_t=2 * 1024, family=family)
            """, select=["RPA070"])
        assert _codes(fs) == ["RPA070"]

    def test_rpa070_silent_when_threaded(self, tmp_path):
        fs = _lint(tmp_path, """
            from repro.kernels import ops

            def f(W, mus, sigmas, family, num_t):
                return ops.frontier_moments(W, mus, sigmas, num_t=num_t,
                                            family=family)
            """, select=["RPA070"])
        assert fs == []

    def test_rpa070_pragma_suppresses(self, tmp_path):
        fs = _lint(tmp_path, """
            from repro.kernels import ops

            def f(W, mus, sigmas, family):
                # repro: allow[RPA070] figure reproduction at pinned rung
                return ops.frontier_moments(W, mus, sigmas, num_t=2048,
                                            family=family)
            """, select=["RPA070"])
        assert fs == []

    def test_rpa070_tests_dir_exempt(self, tmp_path):
        import textwrap

        from repro.analysis import run_paths
        d = tmp_path / "tests"
        d.mkdir()
        (d / "test_fx.py").write_text(textwrap.dedent("""
            from repro.kernels import ops

            def test_f(W, mus, sigmas, family):
                return ops.frontier_moments(W, mus, sigmas, num_t=128,
                                            family=family)
            """))
        fs = run_paths([str(d)], select=["RPA070"])
        assert fs == []


def _lint_serve(tmp_path, source, select=("RPA080",), subdir="serve"):
    """Write one fixture under ``<tmp>/<subdir>/`` and lint it — RPA080
    only patrols files whose path contains a ``serve`` directory."""
    d = tmp_path / subdir
    d.mkdir()
    p = d / "engine_fx.py"
    p.write_text(textwrap.dedent(source))
    return run_paths([str(p)], select=list(select))


_PER_INSTANCE_LOOP = """
    from repro.kernels import ops

    def tick(instances, num_t):
        out = []
        for inst in instances:
            out.append(ops.frontier_moments_with_grads(
                inst.W, inst.mus, inst.sigmas, num_t=num_t,
                family=inst.family))
        return out
    """


class TestServingBatchDiscipline:
    def test_rpa080_fires_on_per_instance_loop(self, tmp_path):
        fs = _lint_serve(tmp_path, _PER_INSTANCE_LOOP)
        assert _codes(fs) == ["RPA080"]

    def test_rpa080_fires_in_comprehension(self, tmp_path):
        fs = _lint_serve(tmp_path, """
            from repro.kernels import ops

            def tick(instances, num_t):
                return [ops.frontier_moments(i.W, i.mus, i.sigmas,
                                             num_t=num_t, family=i.family)
                        for i in instances]
            """)
        assert _codes(fs) == ["RPA080"]

    def test_rpa080_silent_outside_serve_dir(self, tmp_path):
        # the identical per-instance loop is legal off the serving path
        # (e.g. a benchmark's documented looped baseline)
        fs = _lint(tmp_path, _PER_INSTANCE_LOOP, select=["RPA080"])
        assert fs == []

    def test_rpa080_silent_for_stacked_launch(self, tmp_path):
        # the batched idiom: the per-FAMILY-GROUP loop calls the stacked
        # helper, and the kernel entry point sits at top level
        fs = _lint_serve(tmp_path, """
            from repro.kernels import ops

            def row_step(W, mus, sigmas, fam, num_t):
                return ops.frontier_moments_with_grads(
                    W, mus, sigmas, num_t=num_t, family=fam)

            def tick(groups, num_t):
                return [row_step(g.W, g.mus, g.sigmas, g.fam, num_t)
                        for g in groups]
            """)
        assert fs == []

    def test_rpa080_tests_dir_exempt(self, tmp_path):
        d = tmp_path / "serve" / "tests"
        d.mkdir(parents=True)
        p = d / "test_fx.py"
        p.write_text(textwrap.dedent(_PER_INSTANCE_LOOP))
        assert run_paths([str(p)], select=["RPA080"]) == []

    def test_rpa080_pragma_suppresses(self, tmp_path):
        fs = _lint_serve(tmp_path, """
            from repro.kernels import ops

            def tick(instances, num_t):
                out = []
                for inst in instances:
                    # repro: allow[RPA080] documented migration shim
                    out.append(ops.frontier_moments(
                        inst.W, inst.mus, inst.sigmas, num_t=num_t,
                        family=inst.family))
                return out
            """)
        assert fs == []


# ---------------------------------------------------------------------------
# the gate: the real tree lints clean
# ---------------------------------------------------------------------------
class TestSelfClean:
    def test_source_tree_lints_clean(self):
        root = pathlib.Path(__file__).resolve().parents[1]
        fs = run_paths([str(root / "src")])
        assert fs == [], "\n" + format_text(fs)


# ---------------------------------------------------------------------------
# runtime sanitizer
# ---------------------------------------------------------------------------
class TestSanitizerEager:
    @pytest.fixture
    def on(self, monkeypatch):
        monkeypatch.setenv(san.ENV_VAR, "1")

    def _problem(self):
        W = np.asarray([[0.5, 0.3, 0.2]], np.float32)
        mus = np.asarray([10.0, 20.0, 30.0], np.float32)
        sgs = np.asarray([1.0, 2.0, 3.0], np.float32)
        return W, mus, sgs

    def test_disabled_by_default(self, monkeypatch):
        monkeypatch.delenv(san.ENV_VAR, raising=False)
        assert not san.enabled()

    def test_nan_weight_caught_only_when_enabled(self, on, monkeypatch):
        from repro.kernels import ops

        W, mus, sgs = self._problem()
        W_bad = W.copy()
        W_bad[0, 0] = np.nan
        with pytest.raises(san.SanitizeError, match="non-finite"):
            ops.frontier_moments(W_bad, mus, sgs, num_t=128)
        # the unsanitized path silently propagates the NaN into the moments
        monkeypatch.delenv(san.ENV_VAR)
        mu, _ = ops.frontier_moments(W_bad, mus, sgs, num_t=128)
        assert np.isnan(float(mu[0]))

    def test_off_simplex_weight_caught(self, on, monkeypatch):
        from repro.kernels import ops

        W, mus, sgs = self._problem()
        W_bad = W * 2.0  # row mass 2: every downstream moment silently scales
        with pytest.raises(san.SanitizeError, match="row mass"):
            ops.frontier_moments(W_bad, mus, sgs, num_t=128)
        monkeypatch.delenv(san.ENV_VAR)
        mu, _ = ops.frontier_moments(W_bad, mus, sgs, num_t=128)
        assert np.isfinite(float(mu[0]))  # silent wrong answer without tier

    def test_negative_sigma_caught(self, on):
        from repro.kernels import ops

        W, mus, sgs = self._problem()
        with pytest.raises(san.SanitizeError, match="nonneg"):
            ops.frontier_moments(W, mus, -sgs, num_t=128)

    def test_fold_inputs_checked(self, on):
        from repro.core.maxstat import clark_max_moments_seq

        with pytest.raises(san.SanitizeError, match="non-finite"):
            clark_max_moments_seq(np.asarray([1.0, np.nan]),
                                  np.asarray([0.1, 0.1]))

    def test_grads_entry_point_checked(self, on):
        from repro.kernels import ops

        W, mus, sgs = self._problem()
        bad_mus = mus.copy()
        bad_mus[1] = np.inf
        with pytest.raises(san.SanitizeError, match="mus"):
            ops.frontier_moments_with_grads(W, bad_mus, sgs, num_t=128)

    def test_clean_inputs_pass_and_match_unsanitized(self, on, monkeypatch):
        from repro.kernels import ops

        W, mus, sgs = self._problem()
        mu1, var1 = ops.frontier_moments(W, mus, sgs, num_t=128)
        monkeypatch.delenv(san.ENV_VAR)
        mu0, var0 = ops.frontier_moments(W, mus, sgs, num_t=128)
        np.testing.assert_allclose(np.asarray(mu1), np.asarray(mu0))
        np.testing.assert_allclose(np.asarray(var1), np.asarray(var0))


@pytest.mark.sanitizer
class TestSanitizerCheckify:
    """In-trace checkify tier: retraces the solvers, so scripts/ci.sh --fast
    skips these (the --full sanitizer pass runs them)."""

    @pytest.fixture
    def on(self, monkeypatch):
        monkeypatch.setenv(san.ENV_VAR, "1")

    def test_pgd_catches_nan_lam(self, on):
        from jax.experimental.checkify import JaxRuntimeError

        from repro.core.partitioner import optimize_weights

        mus = np.asarray([10.0, 20.0, 30.0], np.float32)
        sgs = np.asarray([1.0, 2.0, 3.0], np.float32)
        with pytest.raises(JaxRuntimeError, match="non-finite"):
            optimize_weights(mus, sgs, lam=float("nan"), steps=4,
                             num_t=128, restarts=0)

    def test_pgd_clean_solve_matches_unsanitized(self, on, monkeypatch):
        from repro.core.partitioner import optimize_weights

        mus = np.asarray([10.0, 20.0, 30.0], np.float32)
        sgs = np.asarray([1.0, 2.0, 3.0], np.float32)
        d1 = optimize_weights(mus, sgs, lam=0.1, steps=8, num_t=128,
                              restarts=1)
        monkeypatch.delenv(san.ENV_VAR)
        d0 = optimize_weights(mus, sgs, lam=0.1, steps=8, num_t=128,
                              restarts=1)
        np.testing.assert_allclose(d1.weights, d0.weights, atol=1e-6)

    def test_dag_solver_catches_nan_lam_var(self, on):
        from jax.experimental.checkify import JaxRuntimeError

        from repro.workflow.dag import Stage, StageDAG
        from repro.workflow.solve import solve_dag

        def mk(name, k, seed):
            r = np.random.default_rng(seed)
            mus = r.uniform(10, 40, k)
            return Stage(name, mus, mus * r.uniform(0.1, 0.4, k))

        dag = StageDAG([mk("a", 3, 0), mk("b", 2, 1)], [("a", "b")])

        with pytest.raises(JaxRuntimeError, match="non-finite"):
            solve_dag(dag, lam_var=float("nan"), steps=4, num_t=128,
                      restarts=0)


# ---------------------------------------------------------------------------
# RPA090/RPA091: observability discipline
# ---------------------------------------------------------------------------
def _lint_repro(tmp_path, source, select, subdir="repro"):
    """Write one fixture under ``<tmp>/repro/`` — RPA090/RPA091 only
    patrol files whose path contains a ``repro`` directory."""
    d = tmp_path / subdir
    d.mkdir(parents=True, exist_ok=True)
    p = d / "mod_fx.py"
    p.write_text(textwrap.dedent(source))
    return run_paths([str(p)], select=list(select))


_FREE_NAME_EMIT = """
    from repro.obs import trace as obs

    def tick():
        with obs.span("engine.my_new_span", rows=3):
            pass
        obs.event("audit.surprise", cause="drift")
    """

_REGISTRY_EMIT = """
    from repro.obs import names as obs_names
    from repro.obs import trace as obs

    def tick():
        with obs.span(obs_names.SPAN_ENGINE_TICK, rows=3):
            pass
        obs.event(obs_names.EV_DIRTY, cause="drift")
    """


class TestObservabilityDiscipline:
    def test_rpa090_fires_on_free_string_names(self, tmp_path):
        fs = _lint_repro(tmp_path, _FREE_NAME_EMIT, select=("RPA090",))
        assert _codes(fs) == ["RPA090", "RPA090"]
        assert "repro.obs.names" in fs[0].message

    def test_rpa090_silent_on_registry_constants(self, tmp_path):
        assert _lint_repro(tmp_path, _REGISTRY_EMIT,
                           select=("RPA090",)) == []

    def test_rpa090_ignores_unrelated_event_calls(self, tmp_path):
        # a sim's own event queue is not an obs emit site
        assert _lint_repro(tmp_path, """
            def drain(queue):
                queue.event("fired", at=3)

            def local():
                def event(name):
                    return name
                return event("fine")
            """, select=("RPA090",)) == []

    def test_rpa090_exempts_obs_package_and_outside_repro(self, tmp_path):
        assert _lint_repro(tmp_path, _FREE_NAME_EMIT, select=("RPA090",),
                           subdir="repro/obs") == []
        assert _lint(tmp_path, _FREE_NAME_EMIT, select=["RPA090"]) == []

    def test_rpa091_bans_wall_clock_in_repro(self, tmp_path):
        fs = _lint_repro(tmp_path, """
            import time

            def span():
                t0 = time.time()
                return time.time() - t0
            """, select=("RPA091",))
        assert _codes(fs) == ["RPA091", "RPA091"]
        assert "perf_counter" in fs[0].message

    def test_rpa091_allows_monotonic_and_pragma(self, tmp_path):
        assert _lint_repro(tmp_path, """
            import time

            def span():
                t0 = time.perf_counter()
                return time.perf_counter() - t0
            """, select=("RPA091",)) == []
        assert _lint_repro(tmp_path, """
            import time

            def artifact_name():
                # repro: allow[RPA091] artifact date stamp, not a duration
                return int(time.time())
            """, select=("RPA091",)) == []

    def test_rpa091_silent_outside_repro(self, tmp_path):
        assert _lint(tmp_path, """
            import time

            def now():
                return time.time()
            """, select=["RPA091"]) == []
