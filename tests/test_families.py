"""Distribution-generic frontier stack: the pluggable completion-time
families (normal / lognormal / drift / empirical) through the quadrature
oracles, the fused kernels, the custom VJP, the solvers, the scheduler, the
simulator and the serving batcher.

Acceptance anchors:
  * lognormal and drift match a numpy Monte-Carlo oracle on (mu, var) to
    <= 1e-3 relative;
  * gradients match finite differences (and autodiff through the family
    quadrature) on all families;
  * frontier_moments / frontier_kch / UncertaintyAwareBalancer accept
    ``family=``;
  * the autotune cache key separates forward/fused/per-family variants and
    survives the v2 -> v3 key-schema bumps;
  * safe_cdf / family point-mass conventions at w=0 are single-sourced and
    right-continuous.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Defective, Drift, Empirical, frontier_kch,
                        get_family, max_moments_quad_w, point_mass_cdf,
                        resolve_family, safe_cdf)
from repro.core import distributions as dists
from repro.core.partitioner import optimize_weights, predict_moments
from repro.kernels import autotune, ops, ref
from repro.kernels.frontier_grid import frontier_grid, frontier_grid_with_grads
from repro.sched import StragglerPolicy, UncertaintyAwareBalancer
from repro.sim import Channel, ClusterSim


def _problem(k, seed=0, cov=(0.05, 0.3)):
    rng = np.random.default_rng(seed)
    mus = rng.uniform(10, 40, k).astype(np.float32)
    sigmas = (mus * rng.uniform(*cov, k)).astype(np.float32)
    return jnp.asarray(mus), jnp.asarray(sigmas)


def _candidates(F, k, seed=0):
    rng = np.random.default_rng(seed)
    e = rng.exponential(size=(F, k))
    return jnp.asarray(e / e.sum(axis=1, keepdims=True), jnp.float32)


def _rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _families(k, seed=0):
    """One spec of each family, with per-channel parameters where they exist."""
    rng = np.random.default_rng(seed)
    mus, sigmas = _problem(k, seed=seed)
    emp = Empirical.from_samples(
        rng.normal(np.asarray(mus)[None, :], np.asarray(sigmas)[None, :],
                   size=(3000, k)))
    return [("normal", "normal"),
            ("lognormal", "lognormal"),
            ("drift", Drift(rng.uniform(0.1, 0.7, k).astype(np.float32))),
            ("empirical", emp),
            ("defective",
             Defective(rng.uniform(0.05, 0.35, k).astype(np.float32),
                       pricing="retry"))]


class TestPhi:
    """The erf-free f32 normal CDF every kernel and oracle evaluates."""

    def test_matches_ndtr_on_dense_grid(self):
        z = jnp.linspace(-10.0, 10.0, 400_001, dtype=jnp.float32)
        err = jnp.max(jnp.abs(dists.Phi(z) - jax.scipy.special.ndtr(z)))
        assert float(err) <= 1e-6

    def test_lower_tail_keeps_relative_accuracy(self):
        # where 0.5 * (1 + erf) would cancel to 0, the erfc form keeps digits
        z = jnp.linspace(-12.0, -5.0, 7001, dtype=jnp.float32)
        want = jax.scipy.special.ndtr(z)
        rel = jnp.max(jnp.abs(dists.Phi(z) - want) / want)
        assert float(rel) <= 1e-4   # f32 rounding of exp(-z^2/2) at z=-12
        assert float(dists.Phi_c(jnp.float32(6.0))) == pytest.approx(
            float(jax.scipy.special.ndtr(jnp.float32(-6.0))), rel=1e-5)

    def test_log_cdf_clip_holds_in_the_lower_tail(self):
        z = jnp.linspace(-40.0, 10.0, 50_001, dtype=jnp.float32)

        def log_cdf(z):
            return jnp.log(jnp.clip(dists.Phi(z), ref._CDF_FLOOR, 1.0))

        lc = log_cdf(z)
        assert bool(jnp.all(jnp.isfinite(lc)))
        assert bool(jnp.all(jnp.diff(lc) >= 0.0))     # monotone
        floor = float(jnp.log(jnp.float32(ref._CDF_FLOOR)))
        assert bool(jnp.all(lc[z <= -14.0] == floor))  # clipped, not -inf
        assert bool(jnp.all(lc[z >= -12.0] > floor))
        g = jax.vmap(jax.grad(log_cdf))(z)
        assert bool(jnp.all(jnp.isfinite(g)))
        assert bool(jnp.all(g[z <= -14.0] == 0.0))

    def test_derivative_is_phi(self):
        z = jnp.linspace(-8.0, 8.0, 1601, dtype=jnp.float32)
        np.testing.assert_allclose(jax.vmap(jax.grad(dists.Phi))(z),
                                   dists.phi(z), rtol=1e-6, atol=0)


class TestMonteCarloOracle:
    """Acceptance: quadrature (mu, var) vs numpy MC ground truth <= 1e-3."""

    @pytest.mark.mc_oracle
    @pytest.mark.parametrize("dist_id", ["lognormal", "drift"])
    def test_matches_mc_oracle(self, dist_id):
        rng = np.random.default_rng(1)
        k = 4
        mus = rng.uniform(10, 40, k)
        sigmas = mus * rng.uniform(0.1, 0.3, k)
        w = rng.dirichlet(np.ones(k))
        extra = (np.full((1, k), 0.6, np.float32) if dist_id == "drift"
                 else np.zeros((1, k), np.float32))
        # streaming MC: N large enough that se(var)/var ~ 4e-4 << 1e-3
        N, chunk = 10_000_000, 1_000_000
        mc = np.random.default_rng(8)
        s = s2 = 0.0
        for _ in range(N // chunk):
            T = dists.family_sample(dist_id, mc, w, mus, sigmas, extra,
                                    chunk).max(axis=1)
            s += T.sum()
            s2 += (T * T).sum()
        mu_mc = s / N
        var_mc = s2 / N - mu_mc * mu_mc
        fam = (Drift(extra[0]) if dist_id == "drift" else dist_id)
        mu_q, var_q = ops.frontier_moments(
            jnp.asarray(w, jnp.float32)[None, :], jnp.asarray(mus, jnp.float32),
            jnp.asarray(sigmas, jnp.float32), num_t=4096, family=fam)
        assert abs(float(mu_q[0]) - mu_mc) / mu_mc <= 1e-3
        assert abs(float(var_q[0]) - var_mc) / var_mc <= 1e-3

    def test_empirical_recovers_normal_moments(self):
        """A mixture fitted on Normal data reproduces the normal family's
        frontier moments (sanity for the EM fit + mixture quadrature)."""
        k = 3
        mus, sigmas = _problem(k, seed=4, cov=(0.1, 0.2))
        rng = np.random.default_rng(0)
        emp = Empirical.from_samples(
            rng.normal(np.asarray(mus)[None, :], np.asarray(sigmas)[None, :],
                       size=(20000, k)))
        W = _candidates(6, k)
        mu_n, var_n = ops.frontier_moments(W, mus, sigmas, num_t=2048)
        mu_e, var_e = ops.frontier_moments(W, mus, sigmas, num_t=2048,
                                           family=emp)
        np.testing.assert_allclose(mu_e, mu_n, rtol=2e-2)
        np.testing.assert_allclose(var_e, var_n, rtol=2e-1)


class TestFamilyGradients:
    @pytest.mark.parametrize("fam_id", ["normal", "lognormal", "drift",
                                        "empirical", "defective"])
    def test_analytic_matches_autodiff(self, fam_id):
        """The fused analytic adjoint == jax.grad through the family
        quadrature, zero-weight rows included."""
        k, F, num_t = 5, 9, 512
        mus, sigmas = _problem(k, seed=3)
        fam = dict(_families(k, seed=3))[fam_id]
        dist_id, extra = resolve_family(fam, k)
        extra = jnp.asarray(extra, jnp.float32)
        W = _candidates(F, k, seed=F).at[0, 0].set(0.0)
        _, _, dmu, dvar = ops.frontier_moments_with_grads(
            W, mus, sigmas, num_t=num_t, family=fam)
        dmu_a = jax.grad(lambda W: jnp.sum(ref.frontier_grid_ref(
            W, mus, sigmas, num_t=num_t, dist_id=dist_id, extra=extra)[0]))(W)
        dvar_a = jax.grad(lambda W: jnp.sum(ref.frontier_grid_ref(
            W, mus, sigmas, num_t=num_t, dist_id=dist_id, extra=extra)[1]))(W)
        assert _rel(dmu, dmu_a) <= 1e-4
        assert _rel(dvar, dvar_a) <= 1e-4
        assert float(dmu[0, 0]) == 0.0  # zero-weight channel: no direct grad

    @pytest.mark.parametrize("fam_id", ["normal", "lognormal", "drift",
                                        "empirical", "defective"])
    def test_finite_differences(self, fam_id):
        """Acceptance: gradients match central differences on all families."""
        k = 5
        mus, sigmas = _problem(k, seed=9)
        fam = dict(_families(k, seed=9))[fam_id]
        w = np.full(k, 1.0 / k, np.float32)
        lam, num_t, eps = 0.05, 1024, 1e-3

        def f(w):
            mu, var = ops.frontier_moments(jnp.asarray(w)[None, :], mus,
                                           sigmas, num_t=num_t, family=fam)
            return float(mu[0] + lam * var[0])

        _, _, dmu, dvar = ops.frontier_moments_with_grads(
            jnp.asarray(w)[None, :], mus, sigmas, num_t=num_t, family=fam)
        g = np.asarray(dmu + lam * dvar)[0]
        # difference the 3 largest-|g| coordinates: central differences on an
        # f32 quadrature have ~2e-6 absolute noise, so small components drown
        # (the autodiff-parity test above carries the digits; this guards
        # sign/scale against an independent evaluation)
        for i in np.argsort(-np.abs(g))[:3]:
            wp, wm = w.copy(), w.copy()
            wp[i] += eps
            wm[i] -= eps
            fd = (f(wp) - f(wm)) / (2 * eps)
            np.testing.assert_allclose(g[i], fd, rtol=5e-2)

    @pytest.mark.parametrize("fam_id", ["lognormal", "drift", "empirical",
                                        "defective"])
    def test_custom_vjp_bitwise(self, fam_id):
        """jax.grad of frontier_moments rides the fused kernel's outputs
        bitwise for every family (the registered custom VJP)."""
        k = 4
        mus, sigmas = _problem(k, seed=5)
        fam = dict(_families(k, seed=5))[fam_id]
        W = _candidates(8, k, seed=2)
        g = jax.grad(lambda W: jnp.sum(ops.frontier_moments(
            W, mus, sigmas, num_t=256, family=fam)[0]))(W)
        _, _, dmu, _ = ops.frontier_moments_with_grads(
            W, mus, sigmas, num_t=256, family=fam)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(dmu))


class TestFamilyKernels:
    @pytest.mark.parametrize("fam_id", ["normal", "lognormal", "drift",
                                        "empirical", "defective"])
    @pytest.mark.parametrize("fused", [False, True])
    def test_pallas_interpret_matches_ref(self, fam_id, fused):
        k, F, num_t, bf = 5, 8, 256, 4
        mus, sigmas = _problem(k, seed=F)
        fam = dict(_families(k, seed=F))[fam_id]
        dist_id, extra = resolve_family(fam, k)
        extra = jnp.asarray(extra, jnp.float32)
        W = _candidates(F, k, seed=k)
        if fused:
            outs_k = frontier_grid_with_grads(W, mus, sigmas, extra,
                                              num_t=num_t, block_f=bf,
                                              interpret=True, dist_id=dist_id)
            outs_r = ref.frontier_grid_with_grads_ref(W, mus, sigmas,
                                                      num_t=num_t,
                                                      dist_id=dist_id,
                                                      extra=extra)
            names = ("mu", "var", "dmu", "dvar")
        else:
            outs_k = frontier_grid(W, mus, sigmas, extra, num_t=num_t,
                                   block_f=bf, interpret=True, dist_id=dist_id)
            outs_r = ref.frontier_grid_ref(W, mus, sigmas, num_t=num_t,
                                           dist_id=dist_id, extra=extra)
            names = ("mu", "var")
        for name, a, b in zip(names, outs_k, outs_r):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4,
                atol=1e-5 * float(np.max(np.abs(np.asarray(b)))) + 1e-12,
                err_msg=f"{fam_id}:{name}")

    def test_drift_rho_zero_is_normal(self):
        """Drift with rho=0 must reduce exactly to the normal family."""
        k = 4
        mus, sigmas = _problem(k, seed=1)
        W = _candidates(6, k)
        out_n = ops.frontier_moments_with_grads(W, mus, sigmas, num_t=512)
        out_d = ops.frontier_moments_with_grads(W, mus, sigmas, num_t=512,
                                                family=Drift(0.0))
        for a, b in zip(out_d, out_n):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-8)

    def test_lognormal_moment_matched_single_channel(self):
        """One channel, full weight: the lognormal is moment-matched to
        (mu, sigma), so the survival integral must return exactly those
        moments — the family changes the SHAPE, not the marginal moments."""
        mu0, sg0 = 25.0, 7.0
        W = jnp.asarray([[1.0]], jnp.float32)
        m, v = ops.frontier_moments(W, jnp.asarray([mu0], jnp.float32),
                                    jnp.asarray([sg0], jnp.float32),
                                    num_t=4096, family="lognormal")
        np.testing.assert_allclose(float(m[0]), mu0, rtol=1e-3)
        np.testing.assert_allclose(float(v[0]), sg0 * sg0, rtol=5e-3)

    def test_lognormal_joint_differs_from_normal(self):
        """Same marginal moments, different shape: the JOINT max moments must
        move measurably at high CoV (the reason the family matters at all)."""
        k = 6
        mus, sigmas = _problem(k, seed=2, cov=(0.2, 0.3))
        W = _candidates(16, k)
        mu_n, var_n = ops.frontier_moments(W, mus, sigmas, num_t=2048)
        mu_l, var_l = ops.frontier_moments(W, mus, sigmas, num_t=2048,
                                           family="lognormal")
        assert float(np.max(np.abs(np.asarray(mu_l) - np.asarray(mu_n))
                            / np.asarray(mu_n))) > 5e-4
        assert float(np.max(np.abs(np.asarray(var_l) - np.asarray(var_n))
                            / np.asarray(var_n))) > 1e-2


class TestFamilySolvers:
    def test_frontier_kch_accepts_families(self):
        mus, sigmas = _problem(5, seed=6)
        for _, fam in _families(5, seed=6):
            res = frontier_kch(np.asarray(mus), np.asarray(sigmas), num_f=32,
                               num_t=512, include_pgd=False, family=fam)
            assert res.efficient.any()
            # spot-check against the family-generic single-split oracle
            i = int(np.argmin(res.mu))
            m, v = max_moments_quad_w(res.f[i], mus, sigmas, num=2048,
                                      family=fam)
            np.testing.assert_allclose(res.mu[i], float(m), rtol=5e-3)

    def test_drift_solver_shifts_work_off_straggler(self):
        """Pricing drift into the objective must move weight away from the
        drifting channel relative to the normal-family solve."""
        mus = np.array([20.0, 20.0, 20.0])
        sigmas = np.array([2.0, 2.0, 2.0])
        rho = np.array([2.5, 0.0, 0.0], np.float32)
        dec_n = optimize_weights(mus, sigmas, lam=0.0, steps=120, restarts=0)
        dec_d = optimize_weights(mus, sigmas, lam=0.0, steps=120, restarts=0,
                                 family=Drift(rho))
        assert dec_d.weights[0] < dec_n.weights[0] - 0.02
        # under the drift model, the drift-aware split beats the oblivious one
        mu_obl, _ = max_moments_quad_w(dec_n.weights, mus, sigmas, num=4096,
                                       family=Drift(rho))
        assert dec_d.mu <= float(mu_obl) + 1e-6

    def test_predict_moments_family(self):
        mus, sigmas = _problem(3, seed=7)
        w = np.full(3, 1.0 / 3)
        m_n, _ = predict_moments(w, mus, sigmas)
        m_d, _ = predict_moments(w, mus, sigmas, family=Drift(1.0))
        assert m_d > m_n  # drift inflates the joint mean


class TestPointMassConventions:
    """Satellite: safe_cdf / family point-mass edge cases, w=0 channels."""

    def test_right_continuous_at_mean(self):
        # the single-sourced convention: 1 exactly AT the mean, 0 below
        assert float(point_mass_cdf(jnp.float32(5.0), 5.0)) == 1.0
        assert float(point_mass_cdf(jnp.float32(4.999999), 5.0)) == 0.0
        assert float(safe_cdf(jnp.float32(5.0), 5.0, 0.0)) == 1.0
        assert float(safe_cdf(jnp.float32(4.0), 5.0, 0.0)) == 0.0
        assert float(safe_cdf(jnp.float32(6.0), 5.0, 0.0)) == 1.0

    @pytest.mark.parametrize("fam_id", ["normal", "lognormal", "drift",
                                        "empirical", "defective"])
    def test_w_zero_channel_is_finished(self, fam_id):
        """A w=0 channel is a point mass at 0: CDF 1 for every t >= 0, so it
        cannot move the joint moments — for ANY family."""
        k = 3
        mus, sigmas = _problem(k, seed=11)
        fam = dict(_families(k, seed=11))[fam_id]
        dist_id, extra = resolve_family(fam, k)
        cdf0 = dists.family_cdf(dist_id, jnp.asarray([0.0, 1.0, 50.0]),
                                jnp.float32(0.0), mus[0], sigmas[0],
                                jnp.asarray(extra, jnp.float32)[:, :1])
        np.testing.assert_array_equal(np.asarray(cdf0), 1.0)
        # joint moments with/without the zero-weight channel agree
        W2 = jnp.asarray([[0.6, 0.4]], jnp.float32)
        W3 = jnp.asarray([[0.6, 0.4, 0.0]], jnp.float32)
        fam2 = (dist_id, jnp.asarray(extra, jnp.float32)[:, :2])
        mu3, var3 = ops.frontier_moments(W3, mus, sigmas, num_t=2048,
                                         family=(dist_id,
                                                 jnp.asarray(extra,
                                                             jnp.float32)))
        mu2, var2 = ops.frontier_moments(W2, mus[:2], sigmas[:2], num_t=2048,
                                         family=fam2)
        np.testing.assert_allclose(mu3, mu2, rtol=1e-5)
        np.testing.assert_allclose(var3, var2, rtol=1e-4, atol=1e-6)

    def test_sigma_zero_channel_is_point_mass_at_mean(self):
        """sigma=0, w>0: deterministic channel at its effective mean; the
        survival integral must see a step there (family-aware safe_cdf)."""
        mus = jnp.asarray([20.0, 30.0], jnp.float32)
        sigmas = jnp.asarray([2.0, 0.0], jnp.float32)
        w = jnp.asarray([0.3, 0.7], jnp.float32)
        m, v = max_moments_quad_w(w, mus, sigmas, num=4096)
        # channel 1 is a point mass at 21 >> channel 0's mean 6 +- 0.6:
        # the max is essentially the constant 21
        np.testing.assert_allclose(float(m), 21.0, rtol=1e-3)
        assert float(v) < 0.1


class TestAutotuneFamilyCache:
    """Satellite: cache keys must separate forward/fused/per-family variants
    and survive the v2 -> v3 key-schema bumps."""

    def test_keys_do_not_collide(self, tmp_path):
        path = str(tmp_path / "cache.json")
        autotune.clear_cache()
        try:
            variants = [(False, "normal"), (True, "normal"),
                        (False, "drift"), (True, "drift"),
                        (False, "lognormal"), (True, "empirical"),
                        (False, "defective"), (True, "defective")]
            keys = {autotune._key(256, 8, 128, "xla", fused, dist)
                    for fused, dist in variants}
            assert len(keys) == len(variants)
            # seed distinct entries through lookup and verify isolation
            # (F=256 so every seeded block_f <= F survives lookup's clamp)
            for i, (fused, dist) in enumerate(variants):
                autotune._CACHE[autotune._key(256, 8, 128, "xla", fused, dist)] = {
                    "block_f": 2 ** (i + 1), "source": "sweep"}
            for i, (fused, dist) in enumerate(variants):
                assert autotune.lookup(256, 8, 128, backend="xla", fused=fused,
                                       dist_id=dist, cache_path=path) == 2 ** (i + 1)
        finally:
            autotune.clear_cache()

    def test_legacy_keys_migrate_as_normal_family(self, tmp_path):
        """A pre-family JSON cache (un-versioned keys) keeps serving its
        swept winners — as normal-family entries — after the schema bump."""
        path = str(tmp_path / "cache.json")
        legacy = {"xla:F8:K3:T64:fused0": {"block_f": 4, "source": "sweep"},
                  "xla:F8:K3:T64:fused1": {"block_f": 2, "source": "sweep"}}
        with open(path, "w") as f:
            json.dump(legacy, f)
        autotune.clear_cache()
        try:
            assert autotune.lookup(8, 3, 64, backend="xla", fused=False,
                                   cache_path=path) == 4
            assert autotune.lookup(8, 3, 64, backend="xla", fused=True,
                                   cache_path=path) == 2
            # other families DON'T inherit the legacy entry (fall to model)
            bf_drift = autotune.lookup(8, 3, 64, backend="xla", fused=True,
                                       dist_id="drift", cache_path=path)
            assert bf_drift == autotune.pick_block_f(8, 3, 64, backend="xla",
                                                     fused=True,
                                                     dist_id="drift")
        finally:
            autotune.clear_cache()

    def test_sweep_round_trip_v3(self, tmp_path):
        path = str(tmp_path / "cache.json")
        autotune.clear_cache()
        try:
            entry = autotune.sweep(8, 3, 64, backend="xla", fused=False,
                                   repeats=1, candidates=(4, 8),
                                   cache_path=path, dist_id="lognormal")
            on_disk = json.load(open(path))
            assert "v4:cpu:xla:F8:K3:T64:modefwd:famlognormal" in on_disk
            autotune.clear_cache()
            assert autotune.lookup(8, 3, 64, backend="xla",
                                   dist_id="lognormal",
                                   cache_path=path) == entry["block_f"]
        finally:
            autotune.clear_cache()

    def test_v2_keys_migrate_with_mode_mapping(self, tmp_path):
        """A v2 JSON cache keeps serving its swept winners after the v3
        (mode-aware) bump: fused0 -> fwd, fused1 -> grad — and the new pgrad
        mode never inherits a v2 entry (its working set is larger; a stale
        fused block could overflow it)."""
        path = str(tmp_path / "cache.json")
        v2 = {"v2:xla:F8:K3:T64:fused0:famdrift": {"block_f": 4,
                                                   "source": "sweep"},
              "v2:xla:F8:K3:T64:fused1:famdrift": {"block_f": 2,
                                                   "source": "sweep"}}
        with open(path, "w") as f:
            json.dump(v2, f)
        autotune.clear_cache()
        try:
            assert autotune.lookup(8, 3, 64, backend="xla", fused=False,
                                   dist_id="drift", cache_path=path) == 4
            assert autotune.lookup(8, 3, 64, backend="xla", fused=True,
                                   dist_id="drift", cache_path=path) == 2
            bf_pgrad = autotune.lookup(8, 3, 64, backend="xla", fused=True,
                                       dist_id="drift", params=True,
                                       cache_path=path)
            assert bf_pgrad == autotune.pick_block_f(
                8, 3, 64, backend="xla", fused=True, dist_id="drift",
                params=True)
        finally:
            autotune.clear_cache()

    def test_pgrad_mode_needs_no_more_room_than_budget(self):
        """The full-parameter launch's working set exceeds the W-grad one, so
        the model's pgrad pick can only shrink — and must still fit VMEM."""
        b_grad = autotune.vmem_bytes(64, 1024, 256, fused=True,
                                     dist_id="lognormal")
        b_pgrad = autotune.vmem_bytes(64, 1024, 256, fused=True,
                                      dist_id="lognormal", params=True)
        assert b_pgrad > b_grad
        bf_g = autotune.pick_block_f(4096, 1024, 256, backend="pallas",
                                     fused=True, dist_id="lognormal")
        bf_p = autotune.pick_block_f(4096, 1024, 256, backend="pallas",
                                     fused=True, dist_id="lognormal",
                                     params=True)
        assert bf_p <= bf_g
        assert autotune.vmem_bytes(bf_p, 1024, 256, fused=True,
                                   dist_id="lognormal", params=True) \
            <= autotune._VMEM_BUDGET_BYTES

    def test_drift_needs_smaller_fused_blocks(self):
        """Drift's four accumulators shrink the model's safe pick vs the
        two-accumulator families at fleet scale."""
        b_norm = autotune.vmem_bytes(64, 1024, 256, fused=True,
                                     dist_id="normal")
        b_drift = autotune.vmem_bytes(64, 1024, 256, fused=True,
                                      dist_id="drift")
        assert b_drift > b_norm
        assert (autotune.pick_block_f(4096, 4096, 256, backend="pallas",
                                      fused=True, dist_id="drift")
                <= autotune.pick_block_f(4096, 4096, 256, backend="pallas",
                                         fused=True, dist_id="normal"))


class TestSimBoundary:
    """Satellite: run_step accepts jax arrays / unnormalized weights and an
    explicit seed/rng."""

    def test_jax_array_and_unnormalized_weights(self):
        sim = ClusterSim.heterogeneous(4, seed=3)
        t1, d1 = sim.run_step(jnp.asarray([2.0, 2.0, 2.0, 2.0]), rng=123)
        sim2 = ClusterSim.heterogeneous(4, seed=3)
        t2, d2 = sim2.run_step(np.asarray([0.25] * 4), rng=123)
        assert t1 == t2
        np.testing.assert_allclose(d1, d2)

    def test_explicit_rng_reproducible_independent_of_history(self):
        sim = ClusterSim.heterogeneous(3, seed=0)
        sim.run_step([1.0, 1.0, 1.0])          # advance internal stream
        t1, _ = sim.run_step([0.5, 0.3, 0.2], rng=7)
        sim2 = ClusterSim.heterogeneous(3, seed=0)
        t2, _ = sim2.run_step([0.5, 0.3, 0.2], rng=7)
        assert t1 == t2

    def test_all_zero_weights_stay_zero(self):
        sim = ClusterSim.heterogeneous(3, seed=1)
        t, d = sim.run_step(np.zeros(3))
        assert t == 0.0 and (d == 0.0).all()

    def test_lognormal_and_drift_fleets_vectorized(self):
        for dist in ("lognormal", "drift"):
            sim = ClusterSim.heterogeneous(64, seed=5, dist=dist)
            t, d = sim.run_step(np.full(64, 1.0 / 64))
            assert t > 0 and (d[d > 0] > 0).all()
        # drift ground truth: higher share -> superlinear duration growth
        # (weights are normalized at the boundary, so a dummy channel holds
        # the remaining share)
        mk = lambda: ClusterSim(channels=[
            Channel(mu=10.0, sigma=1e-9, dist="drift", rho=1.0),
            Channel(mu=1e-6, sigma=1e-12)], seed=0)
        _, d_full = mk().run_step([1.0, 0.0])
        _, d_half = mk().run_step([0.5, 0.5])
        # E[T(1)] = 15, E[T(0.5)] = 6.25: ratio 2.4 >> 2 (linear would be 2)
        assert d_full[0] / d_half[0] > 2.2

    def test_wrong_length_raises(self):
        sim = ClusterSim.heterogeneous(3, seed=1)
        with pytest.raises(ValueError, match="weights"):
            sim.run_step([0.5, 0.5])


class TestSchedulerFamilies:
    def test_balancer_accepts_family(self):
        obs = [np.array([12.0, 20.0, 28.0]), np.array([11.5, 21.0, 27.0]),
               np.array([12.5, 19.5, 29.0])]
        ws = {}
        for fam in ("normal", "lognormal"):
            b = UncertaintyAwareBalancer(3, lam=0.05, pgd_steps=60, family=fam)
            for d in obs:
                b.observe(d, np.full(3, 1.0 / 3))
            ws[fam] = b.weights()
            np.testing.assert_allclose(ws[fam].sum(), 1.0, atol=1e-6)
        # both favor the fast channel; exact weights differ by family
        assert ws["lognormal"][0] > ws["lognormal"][2]

    def test_family_change_invalidates_cached_solve(self):
        b = UncertaintyAwareBalancer(3, lam=0.05, pgd_steps=60,
                                     refresh_every=1000)
        b.observe([10.0, 20.0, 30.0], np.full(3, 1.0 / 3))
        w_n = b.weights()
        w_d = b.weights(family=Drift(np.array([3.0, 0.0, 0.0], np.float32)))
        assert not np.allclose(w_n, w_d)  # refresh_every alone would cache

    def test_min_weight_floor_applies_on_cached_ticks(self):
        """Cached and fresh frontier ticks must return identical
        post-processing: the min_weight floor used to be skipped on the
        cache-hit path."""
        b = UncertaintyAwareBalancer(3, lam=0.01, pgd_steps=60,
                                     refresh_every=50, min_weight=0.15)
        b.observe([1.0, 15.0, 40.0], np.full(3, 1.0 / 3))
        w_fresh = b.weights()   # solve tick (fills the cache)
        w_cached = b.weights()  # cache hit
        np.testing.assert_allclose(w_fresh, w_cached)
        # the floor renormalizes, so the guaranteed lower bound is
        # min_weight / (1 + k * min_weight)
        assert w_fresh.min() >= 0.15 / (1 + 3 * 0.15) - 1e-9

    def test_state_dict_round_trips_family(self):
        b = UncertaintyAwareBalancer(3, lam=0.1, family="lognormal")
        b.observe([10.0, 20.0, 30.0], [1.0, 1.0, 1.0])
        b2 = UncertaintyAwareBalancer.from_state_dict(b.state_dict())
        assert get_family(b2.family).dist_id == "lognormal"
        np.testing.assert_allclose(b.weights(), b2.weights(), atol=1e-6)

    def test_straggler_drift_mitigation_keeps_channel(self):
        """Drift mode: a detected straggler keeps (reduced) work instead of
        being quarantined to zero."""
        b = UncertaintyAwareBalancer(3, lam=0.01, pgd_steps=60)
        pol = StragglerPolicy(b, z_threshold=2.5, mitigation="drift")
        for _ in range(30):
            pol.record([10.0, 10.2, 9.8], np.full(3, 1.0 / 3))
        w_before = pol.weights()
        for _ in range(4):  # channel 0 straggles hard
            pol.record([40.0, 10.2, 9.8], np.full(3, 1.0 / 3))
        assert 0 in pol.drift_rhos and pol.drift_rhos[0] > 0
        assert not pol.quarantined
        w_after = pol.weights()
        assert 0.0 < w_after[0] < w_before[0]  # discounted, not dropped
        # recovery: clean steps decay rho back toward the normal family
        for _ in range(30):
            pol.record([10.0, 10.2, 9.8], np.full(3, 1.0 / 3))
        assert 0 not in pol.drift_rhos

    def test_straggler_quarantine_mode_unchanged(self):
        b = UncertaintyAwareBalancer(2)
        pol = StragglerPolicy(b, z_threshold=2.5, quarantine_after=2)
        for _ in range(30):
            pol.record([10.0, 12.0], [0.5, 0.5])
        for _ in range(3):
            pol.record([10.0, 60.0], [0.5, 0.5])
        assert 1 in pol.quarantined
        assert pol.weights()[1] == 0.0


class TestDefectiveFamily:
    """Tentpole: fault tolerance as channel physics. The defective family
    prices a per-channel attempt-failure probability ``p`` (extra row 0) and
    a retry/resume cost ``lam`` (extra row 1) into retry-inflated per-unit
    moments (a, b); T(w) ~ N(w a, (w b)^2) is a pure scale family, so the
    whole stack treats it like ``normal`` with (a, b) substituted."""

    def test_p_zero_reduces_to_normal(self):
        """p = 0 is the healthy fleet: (a, b) = (mu, sigma) identically, so
        moments AND gradients must agree with the normal family to fp
        round-off (b = sqrt(sigma^2) may differ by an ulp)."""
        k = 4
        mus, sigmas = _problem(k, seed=13)
        W = _candidates(6, k)
        out_n = ops.frontier_moments_with_grads(W, mus, sigmas, num_t=512)
        out_d = ops.frontier_moments_with_grads(W, mus, sigmas, num_t=512,
                                                family=Defective(0.0))
        for a, b in zip(out_d, out_n):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-8)

    def test_rejects_out_of_domain(self):
        with pytest.raises(ValueError, match=r"\[0, 1\]"):
            Defective([-0.1, 0.2])
        with pytest.raises(ValueError, match=r"\[0, 1\]"):
            Defective([0.1, 1.2])
        with pytest.raises(ValueError, match="pricing"):
            Defective(0.1, pricing="refund")
        with pytest.raises(ValueError, match="pricing"):
            Defective(0.1, pricing=1.5)
        with pytest.raises(ValueError, match="failure"):
            get_family("defective")  # p is not optional: build Defective(p)

    def test_pricing_orders_the_cost(self):
        """resume (lam=0.5) re-runs only half an attempt per failure, so it
        must sit strictly between healthy and full-retry pricing."""
        k = 3
        mus, sigmas = _problem(k, seed=17)
        W = _candidates(4, k)
        p = np.full(k, 0.2, np.float32)
        mu_0, _ = ops.frontier_moments(W, mus, sigmas, num_t=512)
        mu_r, _ = ops.frontier_moments(W, mus, sigmas, num_t=512,
                                       family=Defective(p, pricing="resume"))
        mu_f, _ = ops.frontier_moments(W, mus, sigmas, num_t=512,
                                       family=Defective(p, pricing="retry"))
        assert float(np.min(np.asarray(mu_r) - np.asarray(mu_0))) > 0.0
        assert float(np.min(np.asarray(mu_f) - np.asarray(mu_r))) > 0.0

    @pytest.mark.mc_oracle
    def test_per_channel_moments_match_physical_process(self):
        """Acceptance: the analytic (a, b) equal the mean/std of the PHYSICAL
        retry process (failures actually drawn, N ~ Geom) to <= 1e-3."""
        rng = np.random.default_rng(2)
        k = 4
        mus = rng.uniform(10, 40, k)
        sigmas = mus * rng.uniform(0.1, 0.3, k)
        p = np.array([0.0, 0.05, 0.15, 0.4], np.float32)
        lam = 1.0
        w = rng.dirichlet(np.ones(k))
        extra = np.stack([p, np.full(k, lam, np.float32)])
        a, b = dists.defective_moments_np(mus, sigmas, p, lam)
        N, chunk = 20_000_000, 1_000_000
        mc = np.random.default_rng(9)
        s = np.zeros(k)
        s2 = np.zeros(k)
        for _ in range(N // chunk):
            T = dists.family_sample("defective", mc, w, mus, sigmas, extra,
                                    chunk)
            s += T.sum(axis=0)
            s2 += (T * T).sum(axis=0)
        mu_mc = s / N
        var_mc = s2 / N - mu_mc * mu_mc
        np.testing.assert_allclose(w * a, mu_mc, rtol=1e-3)
        np.testing.assert_allclose((w * b) ** 2, var_mc, rtol=1e-3)

    @pytest.mark.mc_oracle
    def test_join_matches_mc_oracle(self):
        """The join quadrature vs MC through the MODEL law (the
        moment-matched Gaussian) <= 1e-3 — same contract as the other
        families' oracle test."""
        rng = np.random.default_rng(3)
        k = 4
        mus = rng.uniform(10, 40, k)
        sigmas = mus * rng.uniform(0.1, 0.3, k)
        p = np.array([0.02, 0.1, 0.25, 0.0], np.float32)
        w = rng.dirichlet(np.ones(k))
        a, b = dists.defective_moments_np(mus, sigmas, p, 1.0)
        N, chunk = 10_000_000, 1_000_000
        mc = np.random.default_rng(10)
        s = s2 = 0.0
        for _ in range(N // chunk):
            T = mc.normal(w * a, w * b, size=(chunk, k)).max(axis=1)
            s += T.sum()
            s2 += (T * T).sum()
        mu_mc = s / N
        var_mc = s2 / N - mu_mc * mu_mc
        mu_q, var_q = ops.frontier_moments(
            jnp.asarray(w, jnp.float32)[None, :], jnp.asarray(mus, jnp.float32),
            jnp.asarray(sigmas, jnp.float32), num_t=4096,
            family=Defective(p, pricing="retry"))
        assert abs(float(mu_q[0]) - mu_mc) / mu_mc <= 1e-3
        assert abs(float(var_q[0]) - var_mc) / var_mc <= 1e-3

    @pytest.mark.mc_oracle
    def test_join_shape_approximation_is_close(self):
        """Against the PHYSICAL process the model inherits the Gaussian
        per-channel shape approximation, so the JOIN tolerance is loose and
        documented (the per-channel moments themselves are exact — see
        test_per_channel_moments_match_physical_process)."""
        rng = np.random.default_rng(4)
        k = 4
        mus = rng.uniform(10, 40, k)
        sigmas = mus * rng.uniform(0.1, 0.2, k)
        p = np.array([0.05, 0.1, 0.15, 0.08], np.float32)
        w = rng.dirichlet(np.ones(k))
        extra = np.stack([p, np.ones(k, np.float32)])
        N, chunk = 2_000_000, 500_000
        mc = np.random.default_rng(11)
        s = s2 = 0.0
        for _ in range(N // chunk):
            T = dists.family_sample("defective", mc, w, mus, sigmas, extra,
                                    chunk).max(axis=1)
            s += T.sum()
            s2 += (T * T).sum()
        mu_mc = s / N
        var_mc = s2 / N - mu_mc * mu_mc
        mu_q, var_q = ops.frontier_moments(
            jnp.asarray(w, jnp.float32)[None, :], jnp.asarray(mus, jnp.float32),
            jnp.asarray(sigmas, jnp.float32), num_t=4096,
            family=Defective(p, pricing="retry"))
        # the join MEAN is what the solver minimizes: within 5% of the
        # physical process. The join VARIANCE under-prices the multimodal
        # retry tail (retries put probability spikes at +mu, +2mu, ... that
        # the moment-matched Gaussian flattens), so only a factor-scale
        # envelope is promised — the per-channel moments are exact, the
        # join shape is an approximation by design.
        assert abs(float(mu_q[0]) - mu_mc) / mu_mc <= 5e-2
        assert 0.3 <= float(var_q[0]) / var_mc <= 1.6

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    @pytest.mark.parametrize("p_edge", [0.0, 0.95])
    def test_p_gradient_matches_fd(self, impl, p_edge):
        """The custom VJP's analytic d/dp (extra row 0) matches finite
        differences on both impls, including the p = 0 healthy edge and the
        p -> 1 retry-divergence edge."""
        rng = np.random.default_rng(6)
        k = 4
        mus = rng.uniform(0.8, 2.0, k).astype(np.float32)
        sigmas = rng.uniform(0.1, 0.4, k).astype(np.float32)
        W = jnp.asarray(rng.dirichlet(np.ones(k), 5), jnp.float32)
        p = np.array([p_edge, 0.1, 0.2, 0.05], np.float32)
        extra = Defective(p, pricing="retry").extra(k)

        def loss(e):
            m, v = ops.frontier_moments(W, mus, sigmas, num_t=512, impl=impl,
                                        family=("defective", e))
            return m.sum() + 0.1 * v.sum()

        g = jax.grad(loss)(jnp.asarray(extra))
        h = 1e-3
        for i in range(k):
            if p[i] == 0.0:
                # one-sided forward difference: stepping to p = -h would
                # leave the family's domain (the sanitizer rejects it, and
                # the analytic grad is the one-sided limit at the boundary)
                ep = extra.copy()
                ep[0, i] += h
                fd = (loss(jnp.asarray(ep)) - loss(jnp.asarray(extra))) / h
            else:
                ep, em = extra.copy(), extra.copy()
                ep[0, i] += h
                em[0, i] -= h
                fd = (loss(jnp.asarray(ep)) - loss(jnp.asarray(em))) / (2 * h)
            np.testing.assert_allclose(float(g[0, i]), float(fd), rtol=5e-2,
                                       err_msg=f"channel {i} (p={p[i]})")

    def test_lam_row_cotangent_is_zero_by_contract(self):
        """Pricing (extra row 1) is a hyperparameter chosen by the retry
        policy, not a fitted quantity: the VJP documents a ZERO cotangent for
        it (only row 0 is populated), so nothing ever descends on lam."""
        k = 3
        mus, sigmas = _problem(k, seed=19)
        W = _candidates(4, k)
        extra = Defective(np.full(k, 0.2, np.float32)).extra(k)
        g = jax.grad(lambda e: jnp.sum(ops.frontier_moments(
            W, mus, sigmas, num_t=256, family=("defective", e))[0]))(
                jnp.asarray(extra))
        np.testing.assert_array_equal(np.asarray(g[1]), 0.0)
        assert float(np.max(np.abs(np.asarray(g[0])))) > 0.0

    def test_autotune_v3_key_round_trip(self, tmp_path):
        path = str(tmp_path / "cache.json")
        autotune.clear_cache()
        try:
            entry = autotune.sweep(8, 3, 64, backend="xla", fused=False,
                                   repeats=1, candidates=(4, 8),
                                   cache_path=path, dist_id="defective")
            on_disk = json.load(open(path))
            assert "v4:cpu:xla:F8:K3:T64:modefwd:famdefective" in on_disk
            autotune.clear_cache()
            assert autotune.lookup(8, 3, 64, backend="xla",
                                   dist_id="defective",
                                   cache_path=path) == entry["block_f"]
        finally:
            autotune.clear_cache()

    def test_solver_shifts_work_off_flaky_channel(self):
        """Pricing the failure physics must move weight away from the flaky
        channel relative to the failure-blind normal solve — the same
        acceptance shape as the drift solver test."""
        mus = np.array([20.0, 20.0, 20.0])
        sigmas = np.array([2.0, 2.0, 2.0])
        p = np.array([0.3, 0.0, 0.0], np.float32)
        dec_n = optimize_weights(mus, sigmas, lam=0.0, steps=120, restarts=0)
        dec_d = optimize_weights(mus, sigmas, lam=0.0, steps=120, restarts=0,
                                 family=Defective(p, pricing="retry"))
        assert dec_d.weights[0] < dec_n.weights[0] - 0.02
        mu_obl, _ = max_moments_quad_w(dec_n.weights, mus, sigmas, num=4096,
                                       family=Defective(p, pricing="retry"))
        assert dec_d.mu <= float(mu_obl) + 1e-6


class TestServeFamilies:
    def test_partitioned_batcher_accepts_family(self):
        from repro.serve.engine import PartitionedBatcher, ReplicaGroup

        groups = [ReplicaGroup(name=f"g{i}") for i in range(3)]
        pb = PartitionedBatcher(groups, lam=0.02, family="lognormal", seed=4)
        assert get_family(pb.balancer.family).dist_id == "lognormal"
        prompts = np.zeros((24, 4), np.int32)
        for _ in range(3):
            join_t, counts, _ = pb.run_batch(prompts)
            assert counts.sum() == 24 and join_t > 0.0
