"""frontier_grid shape sweeps: Pallas (interpret=True) vs the pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from frontier_cases import close, oracle, run, stacked
from repro.kernels import ops, ref
from repro.kernels.frontier_grid import frontier_grid

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("F,K,num_t", [(128, 2, 512), (256, 8, 512), (64, 16, 256)])
def test_frontier_grid_sweep(F, K, num_t):
    W = jax.random.dirichlet(KEY, jnp.ones((K,)), (F,))
    mus = jax.random.uniform(jax.random.fold_in(KEY, 1), (K,), minval=10, maxval=40)
    sgs = jax.random.uniform(jax.random.fold_in(KEY, 2), (K,), minval=0.5, maxval=6)
    m1, v1 = frontier_grid(W, mus, sgs, num_t=num_t, block_f=64, interpret=True)
    m2, v2 = ref.frontier_grid_ref(W, mus, sgs, num_t=num_t)
    np.testing.assert_allclose(m1, m2, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(v1, v2, atol=1e-3, rtol=1e-2)


def test_frontier_grid_matches_core_oracle():
    """Kernel semantics == repro.core.max_moments_quad on the same split."""
    from repro.core import max_moments_quad
    W = jnp.array([[0.4, 0.6]])
    mus, sgs = jnp.array([30.0, 20.0]), jnp.array([2.0, 6.0])
    mk, vk = ops.frontier_moments(jnp.tile(W, (64, 1)), mus, sgs,
                                  num_t=2048, impl="pallas_interpret")
    mq, vq = max_moments_quad(W[0] * mus, W[0] * sgs, num=2048)
    np.testing.assert_allclose(mk[0], mq, rtol=1e-4)
    np.testing.assert_allclose(vk[0], vq, rtol=1e-3)


@pytest.mark.parametrize("mode", ["grad", "pgrad"])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_ragged_stacked_rows_with_extra(impl, mode):
    """11 per-row fleets in blocks of 4: ``ops`` pads the rows, their
    statistics and the drift rates to 12 with copies of row 0, and every
    real row's moments and adjoints are its own, as the oracle gives them
    on the 11 rows unpadded."""
    W, mus, sgs, extra = stacked("drift", 11, 5, seed=3)
    got = run(mode, impl, W, mus, sgs, "drift", extra, 64, block_f=4)
    assert {g.shape[0] for g in got} == {11}
    close(got, oracle(mode, W, mus, sgs, "drift", extra, 64))
