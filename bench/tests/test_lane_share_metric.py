"""The program's lane counters (``profile["launches"]``: ``pack``, ``lanes``),
the ``kernel.lane_share`` reader over them, and the roofline's count of a
lane-packed launch."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as bench_run
from bench import trace_reduce
from bench.kernels import frontier_grid as kfg


def _reader(name="kernel.lane_share.montage"):
    reader, suffix = bench_run.metric_reader(name)
    assert suffix == name.rpartition(".")[2]
    return reader


def _dec(*entries):
    return SimpleNamespace(profile={"launches": list(entries)})


def _entry(launches, rows, pack, lanes, **kw):
    return dict(kw, launches=launches, rows=rows, pack=pack, lanes=lanes)


@pytest.mark.parametrize("name", ["kernel.lane_share.montage",
                                  "kernel.lane_share.solve"])
def test_share_by_hand(name):
    # Montage's ladder: presolve 60 x 33 rows packed 3 in 128 lanes, triage
    # 66 rows unpacked, refine 60 x 11 rows packed 11, final 33 rows packed 3
    dec = _dec(_entry(60, 33, 3, 128), _entry(1, 66, 1, 128),
               _entry(60, 11, 11, 128), _entry(1, 33, 3, 128))
    rec = {"kind": "dag", "log": [(None, dec), (None, dec)]}
    used = 60 * 99 + 66 + 60 * 121 + 99
    assert _reader(name).read(rec, name.rpartition(".")[2]) == \
        pytest.approx(100.0 * used / (122 * 128))


def test_share_is_none_without_the_counters():
    old = _dec({"launches": 3, "rows": 8, "rows_padded": 8, "k": 5,
                "channels": 30, "num_t": 64})
    assert _reader().read({"kind": "dag", "log": [(None, old)]},
                          "montage") is None
    assert _reader().read({"kind": "dag", "log": []}, "montage") is None
    assert _reader().read({"kind": "serve"}, "montage") is None


def _dag(widths):
    from repro.workflow.dag import Stage, StageDAG
    rng = np.random.default_rng(sum(widths))
    stages = []
    for i, k in enumerate(widths):
        mus = rng.uniform(10, 40, k)
        stages.append(Stage(f"s{i}", mus, mus * rng.uniform(0.05, 0.4, k)))
    names = [s.name for s in stages]
    return StageDAG(stages, list(zip(names, names[1:])))


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_launch_counters(impl):
    """Each entry's ``pack`` is what ``ops`` launches with (the XLA path
    packs nothing) and ``lanes`` counts each program's lanes, rounded up
    to 128; the reader over one solve agrees with a count from them."""
    from repro.kernels import autotune
    from repro.workflow import solve_dag

    dag = _dag([40, 1, 24])
    dec = solve_dag(dag, steps=2, restarts=1, num_t=16, eval_num_t=16,
                    presolve_num_t=16, impl=impl)
    used = lanes = 0
    for e in dec.profile["launches"]:
        assert e["k"] == 40 and e["rows_padded"] % e["block_f"] == 0
        want = (1 if impl == "xla"
                else autotune.pack_factor(e["block_f"], e["k"]))
        assert e["pack"] == want
        programs = e["rows_padded"] // e["block_f"]
        assert e["lanes"] == programs * 128 * -(-e["block_f"] * want // 128)
        used += e["launches"] * e["rows"] * e["pack"]
        lanes += e["launches"] * e["lanes"]
    # 3 starts x 3 stages: the presolve's 9 rows pack 5 slots a row
    pre = dec.profile["launches"][0]
    assert pre["phase"] == "presolve" and pre["rows"] == 9
    assert pre["pack"] == (1 if impl == "xla" else 5)
    got = _reader().read({"kind": "dag", "log": [(None, dec)]}, "montage")
    assert got == pytest.approx(100.0 * used / lanes)


def _pallas_avals(fn, *shapes):
    """(first operand, first output) shapes of the one ``pallas_call`` in
    ``fn``'s jaxpr: what the profiler's HLO text gives the launch."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for v in eqn.params.values():
                sub = getattr(v, "jaxpr", None)
                if sub is not None:
                    hit = find(getattr(sub, "jaxpr", sub))
                    if hit is not None:
                        return hit
        return None

    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    eqn = find(jax.make_jaxpr(fn)(*args).jaxpr)
    return eqn.invars[0].aval.shape, eqn.outvars[0].aval.shape


def _hlo(name, arg, out):
    f32 = "f32[{}]".format
    return (f"%{name}.1 = ({f32(','.join(map(str, out)))}{{2,1,0}}) "
            f"custom-call({f32(','.join(map(str, arg)))}{{2,1,0}} %p.1), "
            f"custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("mode,F,K,T", [
    ("grad", 33, 6172, 128),    # Montage presolve: 3 slots a row
    ("grad", 11, 6172, 256),    # Montage refine: 11 slots a row
    ("fwd", 33, 6172, 2048),    # Montage final score
    ("pgrad", 11, 6172, 256),
    ("grad", 45, 329, 256),     # Epigenomics refine: 2 slots a row
    ("fwd", 20, 1000, 64),      # 6 slots a row
])
def test_roofline_counts_a_packed_launch_as_the_unpacked_one(mode, F, K, T):
    """Read through ``trace_reduce.kernel_shape``, a packed launch is F * c
    rows of ceil(K / c) channels; its operations and bytes are the
    unpacked launch's within 1% at these widths (the per-grid-point term,
    counted once a row, is counted once a slot: at tens of channels it is
    more than 1%)."""
    from repro.kernels import autotune
    from repro.kernels.frontier_grid import (frontier_grid,
                                             frontier_grid_with_grads)

    pack = autotune.pack_factor(F, K)
    assert pack > 1
    if mode == "fwd":
        def fn(W, mus, sgs, ex):
            return frontier_grid(W, mus, sgs, ex, num_t=T, block_f=F)
    else:
        def fn(W, mus, sgs, ex):
            return frontier_grid_with_grads(W, mus, sgs, ex, num_t=T,
                                            block_f=F,
                                            param_grads=mode == "pgrad")
    arg, out = _pallas_avals(fn, (F, K), (F, K), (F, K), (1, F, K))
    rows, k = trace_reduce.kernel_shape(
        _hlo(f"frontier_grid_{mode}_normal", arg, out))
    assert (rows, k) == (F * pack, -(-K // pack))
    assert kfg.ops(rows, k, T, mode) == pytest.approx(kfg.ops(F, K, T, mode),
                                                      rel=0.01)
    assert kfg.bytes_moved(rows, k, mode, "normal") == pytest.approx(
        kfg.bytes_moved(F, K, mode, "normal"), rel=0.01)
