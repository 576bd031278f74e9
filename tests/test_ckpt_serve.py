"""The checkpoint store on plain pytrees, and the partitioned batcher."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager, latest_step, restore, save


def _bits(a):
    """A leaf's dtype and raw bytes: equal bits, not merely equal values
    (NaN payloads, signed zeros and bfloat16 all compare exactly)."""
    a = np.asarray(a)
    return a.dtype, a.shape, a.tobytes()


def _assert_bitwise(tree, restored):
    assert (jax.tree_util.tree_structure(tree)
            == jax.tree_util.tree_structure(restored))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert _bits(a) == _bits(b)


class TestCheckpoint:
    def test_roundtrip_exact(self):
        rng = np.random.default_rng(0)
        tree = {"fleet": {"mus": rng.uniform(10, 40, (3, 5)).astype(
                              np.float32),
                          "sigmas": rng.uniform(1, 4, (3, 5)).astype(
                              np.float32)},
                "counts": [np.arange(6, dtype=np.int32),
                           np.int32(7)],
                "seen": (np.array([True, False, True]),)}
        with tempfile.TemporaryDirectory() as d:
            save(d, 7, tree, {"note": "x"})
            assert latest_step(d) == 7
            restored, meta = restore(d, tree)
            assert meta["step"] == 7 and meta["note"] == "x"
            _assert_bitwise(tree, restored)

    def test_atomic_latest_and_gc(self):
        with tempfile.TemporaryDirectory() as d:
            tree = {"w": jnp.ones((4,))}
            for s in (1, 2, 3):
                save(d, s, tree)
            assert latest_step(d) == 3


def _leaves(dtype):
    """A scalar, a vector and a matrix of ``dtype``; the floats hold
    signed zeros, infinities, a NaN and values their dtype rounds."""
    if dtype == jnp.bool_:
        return [np.bool_(True), np.array([True, False, False]),
                np.eye(3, dtype=bool)]
    if dtype == jnp.int32:
        info = np.iinfo(np.int32)
        return [np.int32(-5), np.array([info.min, 0, info.max], np.int32),
                np.arange(12, dtype=np.int32).reshape(3, 4)]
    special = [0.0, -0.0, np.inf, -np.inf, np.nan, 1.0 / 3.0, 1e-30]
    return [jnp.asarray(2.5, dtype), jnp.asarray(special, dtype),
            jax.random.normal(jax.random.PRNGKey(1), (3, 4)).astype(dtype)]


CONTAINERS = {"dict": lambda xs: {f"leaf{i}": x for i, x in enumerate(xs)},
              "list": list, "tuple": tuple}


@pytest.mark.parametrize("container", list(CONTAINERS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32,
                                   jnp.bool_],
                         ids=["float32", "bfloat16", "int32", "bool"])
def test_roundtrip_is_bitwise(dtype, container, tmp_path):
    tree = {"outer": CONTAINERS[container](_leaves(dtype))}
    save(str(tmp_path), 3, tree)
    restored, _ = restore(str(tmp_path), tree)
    _assert_bitwise(tree, restored)


@pytest.mark.parametrize("keep", [1, 2])
def test_latest_step_after_garbage_collection(keep, tmp_path):
    mgr = CheckpointManager(str(tmp_path), interval=1, keep=keep)
    for s in range(1, 6):
        mgr.maybe_save(s, {"w": np.full(3, s, np.float32)}, blocking=True)
    assert latest_step(str(tmp_path)) == 5
    kept = sorted(p for p in os.listdir(str(tmp_path))
                  if p.startswith("step_"))
    assert kept == [f"step_{s:08d}" for s in range(6 - keep, 6)]
    restored, meta = restore(str(tmp_path), {"w": np.zeros(3, np.float32)})
    assert meta["step"] == 5
    np.testing.assert_array_equal(restored["w"], np.full(3, 5.0))
    with pytest.raises(FileNotFoundError):
        restore(str(tmp_path), {"w": np.zeros(3, np.float32)}, step=1)


def test_latest_step_after_gc_with_a_corrupt_pointer(tmp_path):
    """A torn LATEST falls back to the newest step the collector kept."""
    mgr = CheckpointManager(str(tmp_path), interval=1, keep=2)
    for s in range(1, 5):
        mgr.maybe_save(s, {"w": np.zeros(2, np.float32)}, blocking=True)
    with open(os.path.join(str(tmp_path), "LATEST"), "w") as f:
        f.write("step_")
    assert latest_step(str(tmp_path)) == 4


class TestServing:
    def test_partitioned_batcher_learns(self):
        from repro.serve import PartitionedBatcher, ReplicaGroup
        from repro.sim import Channel, ClusterSim
        sim = ClusterSim([Channel(10.0, 0.5), Channel(30.0, 5.0)], seed=0)
        b = PartitionedBatcher([ReplicaGroup("a"), ReplicaGroup("b")], sim=sim)
        for _ in range(40):
            b.run_batch(np.zeros((32, 4), np.int32))
        counts = b.split(32)
        assert counts[0] > counts[1]  # fast replica gets more requests
        assert counts.sum() == 32
