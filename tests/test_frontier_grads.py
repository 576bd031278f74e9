"""The fused moments+gradient path: analytic adjoints vs autodiff through the
quadrature graph, the ``frontier_moments`` custom VJP, the fused Pallas kernel
vs its oracle, and the block_f autotune cache."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.partitioner import objective, optimize_weights
from repro.kernels import autotune, ops, ref
from repro.kernels.frontier_grid import frontier_grid_with_grads


def _problem(k, seed=0, cov=(0.05, 0.3)):
    rng = np.random.default_rng(seed)
    mus = rng.uniform(10, 40, k).astype(np.float32)
    sigmas = (mus * rng.uniform(*cov, k)).astype(np.float32)
    return jnp.asarray(mus), jnp.asarray(sigmas)


def _candidates(F, k, seed=0):
    rng = np.random.default_rng(seed)
    e = rng.exponential(size=(F, k))
    return jnp.asarray(e / e.sum(axis=1, keepdims=True), jnp.float32)


# repro: allow[RPA001] deliberately normal-only autodiff oracle: family
# parity is covered per-dist_id by TestFamilyGradParity below
def _autodiff_grads(W, mus, sigmas, num_t):
    """Per-row (dmu_dW, dvar_dW) by jax.grad through the OLD quadrature
    objective (rows are independent, so grad-of-sum is the per-row grad)."""
    dmu = jax.grad(lambda W: jnp.sum(
        ref.frontier_grid_ref(W, mus, sigmas, num_t=num_t)[0]))(W)
    dvar = jax.grad(lambda W: jnp.sum(
        ref.frontier_grid_ref(W, mus, sigmas, num_t=num_t)[1]))(W)
    return dmu, dvar


def _rel(a, b):
    """Frobenius-norm relative error (the gradient-parity metric)."""
    a, b = np.asarray(a), np.asarray(b)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


class TestGradParity:
    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    @pytest.mark.parametrize("k,F", [(2, 7), (5, 12), (16, 9)])
    def test_analytic_matches_autodiff(self, impl, k, F):
        """Acceptance: fused analytic VJP == jax.grad through the old
        quadrature objective to <= 1e-4 relative, on both backends."""
        mus, sigmas = _problem(k, seed=k)
        W = _candidates(F, k, seed=F)
        num_t = 512
        mu, var, dmu, dvar = ops.frontier_moments_with_grads(
            W, mus, sigmas, num_t=num_t, impl=impl, block_f=4)
        m_ref, v_ref = ref.frontier_grid_ref(W, mus, sigmas, num_t=num_t)
        np.testing.assert_allclose(mu, m_ref, rtol=1e-5)
        np.testing.assert_allclose(var, v_ref, rtol=1e-4, atol=1e-6)
        dmu_a, dvar_a = _autodiff_grads(W, mus, sigmas, num_t)
        assert _rel(dmu, dmu_a) <= 1e-4
        assert _rel(dvar, dvar_a) <= 1e-4

    def test_custom_vjp_routes_through_analytic_path(self):
        """jax.grad of frontier_moments consumes the registered custom VJP —
        identical (bitwise) to the fused kernel's gradient outputs."""
        mus, sigmas = _problem(6, seed=1)
        W = _candidates(10, 6, seed=2)
        g = jax.grad(lambda W: jnp.sum(
            ops.frontier_moments(W, mus, sigmas, num_t=256)[0]))(W)
        _, _, dmu, _ = ops.frontier_moments_with_grads(
            W, mus, sigmas, num_t=256)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(dmu))

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    def test_objective_grad_matches_old_autodiff(self, impl):
        """The PGD objective's gradient (now analytic) agrees with autodiff
        through the pristine quadrature graph."""
        mus, sigmas = _problem(8, seed=3)
        w = jnp.full((8,), 1.0 / 8)
        lam = 0.07
        g_new = jax.grad(objective)(w, mus, sigmas, lam, 512)
        dmu_a, dvar_a = _autodiff_grads(w[None, :], mus, sigmas, 512)
        g_old = (dmu_a + lam * dvar_a)[0]
        assert _rel(g_new, g_old) <= 1e-4

    def test_zero_weight_and_argmax_edge(self):
        """w_k = 0 channels get zero direct gradient; the argmax channel
        carries the moving-grid (tmax) term — parity must survive both."""
        mus = jnp.asarray([20.0, 20.0, 30.0, 10.0], jnp.float32)
        sigmas = jnp.asarray([5.0, 5.0, 1.0, 2.0], jnp.float32)
        W = jnp.asarray([[0.0, 0.5, 0.25, 0.25],
                         [0.25, 0.25, 0.25, 0.25]], jnp.float32)
        _, _, dmu, dvar = ops.frontier_moments_with_grads(
            W, mus, sigmas, num_t=512)
        dmu_a, dvar_a = _autodiff_grads(W, mus, sigmas, 512)
        assert _rel(dmu, dmu_a) <= 1e-4
        assert _rel(dvar, dvar_a) <= 1e-4
        assert float(dmu[0, 0]) == 0.0  # zero-weight channel, not argmax

    def test_finite_difference_spot_check(self):
        """Central differences on a few coordinates (f32 quadrature => loose
        tolerance; this guards the sign/scale of the adjoint, autodiff parity
        above guards the digits)."""
        k = 5
        mus, sigmas = _problem(k, seed=9)
        w = np.full(k, 1.0 / k, np.float32)
        # f is ~8 in f32, so one ulp of it moves a central difference by
        # ulp / (2 eps); at eps=2e-3 that is ~1% of the smallest coordinate
        # checked (~0.014), while the eps^2 truncation stays below 1% too
        lam, num_t, eps = 0.05, 1024, 2e-3

        def f(w):
            mu, var = ops.frontier_moments(jnp.asarray(w)[None, :], mus,
                                           sigmas, num_t=num_t)
            return float(mu[0] + lam * var[0])

        _, _, dmu, dvar = ops.frontier_moments_with_grads(
            jnp.asarray(w)[None, :], mus, sigmas, num_t=num_t)
        g = np.asarray(dmu + lam * dvar)[0]
        for i in range(3):
            wp, wm = w.copy(), w.copy()
            wp[i] += eps
            wm[i] -= eps
            fd = (f(wp) - f(wm)) / (2 * eps)
            np.testing.assert_allclose(g[i], fd, rtol=2e-2)

    def test_mus_sigmas_carry_real_cotangents(self):
        """The closed estimation loop: channel-statistic cotangents are no
        longer stop-grads — jax.grad of frontier_moments w.r.t. mus/sigmas
        matches autodiff through the quadrature graph (the full battery,
        families x impls x edges, lives in tests/test_sensitivity.py)."""
        mus, sigmas = _problem(4, seed=5)
        W = _candidates(3, 4)
        gm = jax.grad(lambda m: jnp.sum(
            ops.frontier_moments(W, m, sigmas, num_t=512)[0]))(mus)
        gs = jax.grad(lambda s: jnp.sum(
            ops.frontier_moments(W, mus, s, num_t=512)[1]))(sigmas)
        assert np.any(np.asarray(gm)) and np.any(np.asarray(gs))
        am = jax.grad(lambda m: jnp.sum(
            ref.frontier_grid_ref(W, m, sigmas, num_t=512)[0]))(mus)
        as_ = jax.grad(lambda s: jnp.sum(
            ref.frontier_grid_ref(W, mus, s, num_t=512)[1]))(sigmas)
        assert _rel(gm, am) <= 1e-4
        assert _rel(gs, as_) <= 1e-4


class TestFusedKernel:
    @pytest.mark.parametrize("F,k,bf,num_t", [(8, 5, 4, 256), (12, 16, 4, 512),
                                              (6, 2, 6, 1024)])
    def test_kernel_matches_oracle(self, F, k, bf, num_t):
        mus, sigmas = _problem(k, seed=F)
        W = _candidates(F, k, seed=k)
        outs_k = frontier_grid_with_grads(W, mus, sigmas, num_t=num_t,
                                          block_f=bf, interpret=True)
        outs_r = ref.frontier_grid_with_grads_ref(W, mus, sigmas, num_t=num_t)
        for name, a, b in zip(("mu", "var", "dmu", "dvar"), outs_k, outs_r):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4,
                atol=1e-5 * float(np.max(np.abs(np.asarray(b)))) + 1e-12,
                err_msg=name)

    def test_block_divisibility_is_a_value_error(self):
        """Satellite: a real ValueError (not a -O-stripped assert), carrying
        both values, for callers that bypass ops.py's padding."""
        W = _candidates(6, 3)
        mus, sigmas = _problem(3)
        with pytest.raises(ValueError, match="F=6.*block_f=4"):
            frontier_grid_with_grads(W, mus, sigmas, num_t=64, block_f=4,
                                     interpret=True)

    def test_pgd_consumes_fused_grads_on_both_impls(self):
        """optimize_weights solves THROUGH the fused path under each impl and
        lands on the same weights."""
        mus, sigmas = _problem(6, seed=11)
        decs = {impl: optimize_weights(mus, sigmas, lam=0.05, steps=80,
                                       restarts=0, impl=impl)
                for impl in ("xla", "pallas_interpret")}
        np.testing.assert_allclose(decs["pallas_interpret"].weights,
                                   decs["xla"].weights, atol=1e-3)


class TestAutotuneCache:
    def test_cache_round_trip(self, tmp_path):
        """Sweep -> JSON -> fresh process (cleared in-process cache) -> lookup
        returns the swept winner, not the model pick."""
        path = str(tmp_path / "autotune_cache.json")
        entry = autotune.sweep(8, 3, 64, backend="xla", repeats=1,
                               candidates=(4, 8), cache_path=path)
        assert entry["source"] == "sweep" and entry["block_f"] in (4, 8)
        on_disk = json.load(open(path))
        key = "v4:cpu:xla:F8:K3:T64:modefwd:famnormal"
        assert on_disk[key]["block_f"] == entry["block_f"]
        autotune.clear_cache()
        assert autotune.lookup(8, 3, 64, backend="xla",
                               cache_path=path) == entry["block_f"]
        autotune.clear_cache()  # leave no tmp-path state for other tests

    def test_model_prefers_smaller_blocks_for_fused(self):
        """The fused kernel's ~3x accumulator footprint must shrink the
        model's pick at fleet scale (the PR 1 block_f=128 regression guard)."""
        fwd = autotune.pick_block_f(4096, 1024, 256, backend="pallas",
                                    fused=False)
        fused = autotune.pick_block_f(4096, 1024, 256, backend="pallas",
                                      fused=True)
        assert fused <= fwd
        assert autotune.vmem_bytes(fused, 1024, 256, fused=True) \
            <= autotune._VMEM_BUDGET_BYTES

    def test_unconstrained_shapes_autotune_silently(self):
        """block_f=None end-to-end: frontier_moments resolves a launch shape
        from the cache/model and matches the explicit-block_f result."""
        mus, sigmas = _problem(5, seed=7)
        W = _candidates(40, 5)
        mu_a, var_a = ops.frontier_moments(W, mus, sigmas, num_t=128)
        mu_e, var_e = ops.frontier_moments(W, mus, sigmas, num_t=128,
                                           block_f=8)
        np.testing.assert_allclose(mu_a, mu_e, rtol=1e-5)
        # var re-fuses differently per launch shape; f32 cancellation noise
        np.testing.assert_allclose(var_a, var_e, rtol=2e-4, atol=1e-6)
