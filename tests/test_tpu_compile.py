"""Compile the frontier kernels for a described TPU v5e; no chip needed.

The TPU compiler ships with the installed jaxlib and compiles for a chip
that is described, not attached. These compiles catch what Pallas
interpret mode cannot: constructs Mosaic does not lower, and programs that
overflow VMEM. Every test here skips where no v5e topology can be
described. The topology is described inside a fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.distributions import extra_rows
from repro.kernels import autotune
from repro.kernels.frontier_grid import frontier_grid, frontier_grid_with_grads

FLEET = dict(F=4096, K=1024, T=256)   # the cluster_scale kernel tick


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dist_id", ["normal", "drift"])
@pytest.mark.parametrize("mode", ["fwd", "grad", "pgrad"])
def test_fleet_kernel_compiles_at_picked_block(one_chip, mode, dist_id):
    """The block the autotune model picks at fleet width compiles: Mosaic
    lowers every op and the program fits the scoped-VMEM limit."""
    F, K, T = FLEET["F"], FLEET["K"], FLEET["T"]
    bf = autotune.pick_block_f(F, K, T, "pallas", fused=mode != "fwd",
                               dist_id=dist_id, params=mode == "pgrad")
    if mode == "fwd":
        def fn(W, mus, sgs, ex):
            return frontier_grid(W, mus, sgs, ex, num_t=T, block_f=bf,
                                 dist_id=dist_id)
    else:
        def fn(W, mus, sgs, ex):
            return frontier_grid_with_grads(W, mus, sgs, ex, num_t=T,
                                            block_f=bf, dist_id=dist_id,
                                            param_grads=mode == "pgrad")
    text = _compile_text(fn, [(F, K), (K,), (K,), (extra_rows(dist_id), K)],
                         one_chip)
    assert "tpu_custom_call" in text


def test_engine_stacked_grad_launch_compiles(one_chip):
    """The serving engine's launch: per-row statistics, K=6 channels,
    T=128, the rows padded to one row bucket."""
    F, K, T, dist_id = 256, 6, 128, "lognormal"
    assert autotune.bucket_rows(200) == F
    bf = autotune.lookup(F, K, T, backend="pallas", fused=True,
                         dist_id=dist_id, stacked=True)

    def fn(W, mus, sgs, ex):
        return frontier_grid_with_grads(W, mus, sgs, ex, num_t=T, block_f=bf,
                                        dist_id=dist_id)

    text = _compile_text(fn, [(F, K), (F, K), (F, K),
                              (extra_rows(dist_id), F, K)], one_chip)
    assert "tpu_custom_call" in text
