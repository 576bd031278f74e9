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


# every launch shape of a Montage 8-degree solve (bench/configs/montage.json:
# 11 stages, the widest of 6,172 channels, 3 starts, one survivor and three)
MONTAGE_K = 6172
MONTAGE_RUNGS = [("grad", 33, 128), ("fwd", 66, 128), ("grad", 11, 256),
                 ("grad", 33, 256), ("fwd", 33, 2048), ("fwd", 99, 2048),
                 ("pgrad", 11, 256), ("pgrad", 99, 256)]


@pytest.mark.parametrize("mode,F,T", MONTAGE_RUNGS)
def test_montage_rung_compiles_packed(one_chip, mode, F, T):
    """The packed launch of each rung compiles within the scoped-VMEM
    limit, and the profiler's name for it reads as F * pack rows of
    ceil(K / pack) channels: the roofline's count of the packed launch is
    the unpacked launch's within 1%."""
    from bench import trace_reduce
    from bench.kernels import frontier_grid as kfg

    K = MONTAGE_K
    fused, params = mode != "fwd", mode == "pgrad"
    bf = autotune.pick_block_f(F, K, T, "pallas", fused=fused, params=params)
    pack = autotune.pack_factor(bf, K)
    Fp = -(-F // bf) * bf
    if mode == "fwd":
        def fn(W, mus, sgs, ex):
            return frontier_grid(W, mus, sgs, ex, num_t=T, block_f=bf)
    else:
        def fn(W, mus, sgs, ex):
            return frontier_grid_with_grads(W, mus, sgs, ex, num_t=T,
                                            block_f=bf, param_grads=params)
    text = _compile_text(fn, [(Fp, K)] * 3 + [(1, Fp, K)], one_chip)
    line = next(ln.strip() for ln in text.splitlines()
                if "custom-call(" in ln and "frontier_grid_" in ln)
    rows, k = trace_reduce.kernel_shape(line)
    assert (rows, k) == (Fp * pack, -(-K // pack))
    assert kfg.ops(rows, k, T, mode) == pytest.approx(
        kfg.ops(Fp, K, T, mode), rel=0.01)
    assert kfg.bytes_moved(rows, k, mode, "normal") == pytest.approx(
        kfg.bytes_moved(Fp, K, mode, "normal"), rel=0.01)


# sha256 (first 16 hex digits) of the Mosaic module, printed without debug
# locations, that the kernel as it was before lane packing lowered to for a
# v5e at these shapes. Each gives pack 1: a block that fills its lanes, or
# K too short to split.
_UNPACKED_MOSAIC = {("grad", 135, 329, 128, 135): "02bc203c71fa45cc",
                    ("fwd", 4096, 1024, 256, 512): "24a015ab07bf20db",
                    ("pgrad", 128, 1024, 256, 128): "858761eec645a1a8",
                    ("grad", 64, 12, 64, 8): "f830e7fc59129c14"}


def _mosaic_text(lowered) -> str:
    """The Mosaic module of the one ``tpu_custom_call`` in ``lowered``."""
    import base64
    import json

    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir

    configs = []

    def visit(op):
        if op.name == "stablehlo.custom_call":
            configs.append(ir.StringAttr(op.attributes["backend_config"]).value)
        for region in op.regions:
            for block in region.blocks:
                for child in block.operations:
                    visit(child.operation)

    visit(lowered.compiler_ir().operation)
    assert len(configs) == 1
    body = base64.b64decode(json.loads(configs[0])["custom_call_config"]["body"])
    ctx = jmlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        return ir.Module.parse(body).operation.get_asm(enable_debug_info=False)


@pytest.mark.parametrize("mode,F,K,T,bf", sorted(_UNPACKED_MOSAIC))
def test_pack_one_lowers_to_the_unpacked_program(one_chip, mode, F, K, T, bf):
    import hashlib

    assert autotune.pack_factor(bf, K) == 1
    if mode == "fwd":
        def fn(W, mus, sgs, ex):
            return frontier_grid(W, mus, sgs, ex, num_t=T, block_f=bf)
    else:
        def fn(W, mus, sgs, ex):
            return frontier_grid_with_grads(W, mus, sgs, ex, num_t=T,
                                            block_f=bf,
                                            param_grads=mode == "pgrad")
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in [(F, K)] * 3 + [(1, F, K)]]
    text = _mosaic_text(jax.jit(fn).lower(*args))
    digest = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert digest == _UNPACKED_MOSAIC[(mode, F, K, T, bf)]
