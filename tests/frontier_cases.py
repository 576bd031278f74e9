"""Stacked ``frontier_grid`` problems and their comparison with the
``kernels/ref.py`` oracle, shared by the kernel tests."""
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def stacked(dist_id, F, K, seed=0, cov=(0.05, 0.4)):
    """F rows of K channels, each row its own fleet: W (F, K), mus and
    sigmas (F, K) and the family's extra (E, F, K), as the workflow solver
    stacks its stages."""
    from repro.core.distributions import resolve_family
    from workflow_shapes import family_spec

    rng = np.random.default_rng(seed)
    e = rng.exponential(size=(F, K))
    W = (e / e.sum(1, keepdims=True)).astype(np.float32)
    mus = rng.uniform(10, 40, (F, K)).astype(np.float32)
    sgs = (mus * rng.uniform(*cov, (F, K))).astype(np.float32)
    extra = np.stack([np.asarray(resolve_family(
        family_spec(dist_id, mus[f], sgs[f], rng), K)[1], np.float32)
        for f in range(F)], axis=1)
    return W, mus, sgs, extra


def run(mode, impl, W, mus, sgs, dist_id, extra, num_t, block_f=None):
    fam = (dist_id, jnp.asarray(extra))
    if mode == "fwd":
        return ops.frontier_moments(W, mus, sgs, num_t=num_t, impl=impl,
                                    block_f=block_f, family=fam)
    return ops.frontier_moments_with_grads(
        W, mus, sgs, num_t=num_t, impl=impl, block_f=block_f, family=fam,
        param_grads=mode == "pgrad")


def close(got, want):
    """Kernel against oracle, each output to 1e-4 of itself or 1e-5 of its
    own largest entry, and to 1e-6 of the launch's largest output: an
    adjoint that cancels to near zero (a mean's sensitivity to a spread)
    carries the float32 error of the terms it cancels from."""
    assert len(got) == len(want)
    scale = max(float(np.max(np.abs(np.asarray(w)))) for w in want)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape
        np.testing.assert_allclose(
            g, w, rtol=1e-4,
            atol=1e-5 * float(np.max(np.abs(w))) + 1e-6 * scale)


def oracle(mode, W, mus, sgs, dist_id, extra, num_t):
    if mode == "fwd":
        return ref.frontier_grid_ref(W, mus, sgs, num_t=num_t,
                                     dist_id=dist_id, extra=extra)
    return ref.frontier_grid_with_grads_ref(
        W, mus, sgs, num_t=num_t, dist_id=dist_id, extra=extra,
        param_grads=mode == "pgrad")
