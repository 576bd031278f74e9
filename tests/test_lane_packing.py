"""Lane-packed ``frontier_grid`` launches (Pallas interpret mode on the CPU).

A launch of few rows deals each row's channels over ``pack`` lane slots,
``kernels.autotune.pack_factor`` of its block and channel count. The packed
launch must give what the unpacked one (the same rows in a block that
fills its lanes) and the ``kernels/ref.py`` oracle give, in every family
and mode: with K not a multiple of the pack (padding channels), with the
rows' slots short of a full lane group, and with the reach argmax tied
across slots. A launch whose shapes give ``pack = 1`` is the pre-packing
launch, bit for bit.
"""
import hashlib

import jax
import numpy as np
import pytest

from repro.core.distributions import (Defective, Drift, Empirical,
                                      resolve_family)
from repro.kernels import autotune, ops, ref
from repro.kernels.frontier_grid import frontier_grid, frontier_grid_with_grads

FAMILIES = ("normal", "lognormal", "drift", "empirical", "defective")
MODES = ("fwd", "grad", "pgrad")


def _family(dist_id, K, rng, mus, sgs):
    if dist_id == "drift":
        return Drift(rng.uniform(0.0, 0.5, K).astype(np.float32))
    if dist_id == "defective":
        return Defective(rng.uniform(0.0, 0.3, K).astype(np.float32))
    if dist_id == "empirical":
        return Empirical.from_samples(
            rng.normal(mus[None, :], sgs[None, :], size=(256, K)))
    return dist_id


def _problem(dist_id, F, K, seed=0, per_row=False):
    """(W, mus, sigmas, extra) of F candidate splits over K channels; the
    statistics shared, or (F, K) per row with extra (E, F, K)."""
    rng = np.random.default_rng(seed)
    mus = rng.uniform(10, 40, K).astype(np.float32)
    sgs = (mus * rng.uniform(0.05, 0.4, K)).astype(np.float32)
    _, extra = resolve_family(_family(dist_id, K, rng, mus, sgs), K)
    extra = np.asarray(extra, np.float32)
    e = rng.exponential(size=(F, K))
    W = (e / e.sum(1, keepdims=True)).astype(np.float32)
    if per_row:
        scale = rng.uniform(0.8, 1.25, (F, 1)).astype(np.float32)
        mus, sgs = mus[None] * scale, sgs[None] * scale
        extra = np.broadcast_to(extra[:, None, :],
                                (extra.shape[0], F, K)).copy()
    return W, mus, sgs, extra


def _launch(mode, W, mus, sgs, extra, dist_id, num_t, bf):
    kw = dict(num_t=num_t, block_f=bf, interpret=True, dist_id=dist_id)
    if mode == "fwd":
        return frontier_grid(W, mus, sgs, extra, **kw)
    return frontier_grid_with_grads(W, mus, sgs, extra,
                                    param_grads=mode == "pgrad", **kw)


def _unpacked(mode, W, mus, sgs, extra, dist_id, num_t):
    """The same rows launched unpacked: repeated to one block of 128 rows,
    which fills its lanes (pack 1), and cut back to the first F."""
    F, K = W.shape
    assert autotune.pack_factor(128, K) == 1
    reps = -(-128 // F)
    rows = lambda a, axis: np.concatenate([a] * reps, axis)[
        (slice(None),) * axis + (slice(0, 128),)]
    if np.ndim(mus) == 2:
        mus, sgs, extra = rows(mus, 0), rows(sgs, 0), rows(extra, 1)
    outs = _launch(mode, rows(W, 0), mus, sgs, extra, dist_id, num_t, 128)
    return tuple(np.asarray(o)[:F] for o in outs)


def _oracle(mode, W, mus, sgs, extra, dist_id, num_t):
    if mode == "fwd":
        return ref.frontier_grid_ref(W, mus, sgs, num_t=num_t,
                                     dist_id=dist_id, extra=extra)
    return ref.frontier_grid_with_grads_ref(
        W, mus, sgs, num_t=num_t, dist_id=dist_id, extra=extra,
        param_grads=mode == "pgrad")


def _close(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(
        a, b, rtol=1e-4, atol=1e-5 * float(np.max(np.abs(b))) + 1e-12,
        err_msg=what)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("dist_id", FAMILIES)
def test_packed_matches_unpacked_and_oracle(dist_id, mode):
    """Six rows of 37 channels in one block: the shapes pack five slots a
    row (K = 37 pads to 40), thirty of the block's 128 lanes; per-row
    statistics as the DAG solver stacks them."""
    F, K, T = 6, 37, 32
    assert autotune.pack_factor(F, K) == 5
    W, mus, sgs, extra = _problem(dist_id, F, K, seed=3, per_row=True)
    packed = _launch(mode, W, mus, sgs, extra, dist_id, T, F)
    plain = _unpacked(mode, W, mus, sgs, extra, dist_id, T)
    oracle = _oracle(mode, W, mus, sgs, extra, dist_id, T)
    assert len(packed) == len(plain) == len(oracle)
    for i, (p, u, o) in enumerate(zip(packed, plain, oracle)):
        assert np.asarray(p).shape == np.asarray(u).shape
        _close(p, u, f"{dist_id}/{mode} output {i}: packed vs unpacked")
        _close(p, o, f"{dist_id}/{mode} output {i}: packed vs oracle")


@pytest.mark.parametrize("K,pack", [(20, 2), (23, 3), (30, 4), (50, 7)])
@pytest.mark.parametrize("mode", MODES)
def test_shared_statistics_any_pack(mode, K, pack):
    """Shared channel statistics, two programs of 5 rows, channel counts
    that pack 2, 3, 4 and 7 slots a row, dividing K and not."""
    F, T, bf = 10, 32, 5
    assert autotune.pack_factor(bf, K) == pack
    W, mus, sgs, extra = _problem("drift", F, K, seed=pack)
    packed = _launch(mode, W, mus, sgs, extra, "drift", T, bf)
    oracle = _oracle(mode, W, mus, sgs, extra, "drift", T)
    for i, (p, o) in enumerate(zip(packed, oracle)):
        _close(p, o, f"drift/{mode} pack {pack} output {i}")


@pytest.mark.parametrize("mode", ["grad", "pgrad"])
def test_reach_argmax_tie_across_slots(mode):
    """Channels 0 and 30 have the same statistics and weight, and the
    largest reach: a tie between slot 0 and slot 3 of a 5-slot row
    (Kc = 8). The moving-grid cotangent splits evenly over the tie, as the
    unpacked launch splits it, and the moments and split adjoints are the
    oracle's. (At a tie the oracle's autodiff of the variance's statistic
    adjoints already parts from the unpacked kernel's, by ~1e-3 of the
    largest; those are held to the unpacked launch.)"""
    F, K, T = 4, 37, 64
    assert autotune.pack_factor(F, K) == 5
    W, mus, sgs, extra = _problem("normal", F, K, seed=11)
    mus = mus.copy()
    sgs = sgs.copy()
    mus[[0, 30]] = 80.0
    sgs[[0, 30]] = 20.0
    W = 0.6 * W / (W.sum(1, keepdims=True) - W[:, [0]] - W[:, [30]])
    W[:, [0, 30]] = 0.2
    reach = W * mus + 10.0 * W * sgs
    assert np.all(np.argmax(reach, axis=1) == 0)
    assert np.all(reach[:, 0] == reach[:, 30])
    packed = _launch(mode, W, mus, sgs, extra, "normal", T, F)
    plain = _unpacked(mode, W, mus, sgs, extra, "normal", T)
    oracle = _oracle(mode, W, mus, sgs, extra, "normal", T)
    for i, (p, u) in enumerate(zip(packed, plain)):
        _close(p, u, f"tie output {i}: packed vs unpacked")
    for i, (p, o) in enumerate(zip(packed[:4], oracle[:4])):
        _close(p, o, f"tie output {i}: packed vs oracle")
    # the tied channels share the tmax term: equal gradients on each row
    dmu = np.asarray(packed[2])
    np.testing.assert_array_equal(dmu[:, 0], dmu[:, 30])


# sha256 (first 16 hex digits) of the outputs of the kernel as it was
# before lane packing, on the problems of test_pack_one_is_the_unpacked_launch
# (interpret mode, float32 bytes of every output in order). Their shapes
# give pack 1: K too short to split (12 channels), and a block that fills
# its 128 lanes.
_PROBLEMS = {"short_k": (8, 12, 4), "full_block": (256, 19, 128)}
_UNPACKED = {("short_k", "normal", "fwd"): "69ad73b81cb83c19",
             ("short_k", "normal", "grad"): "e0f01c2b1f553f83",
             ("short_k", "normal", "pgrad"): "71fdb3893983e9d7",
             ("short_k", "drift", "fwd"): "06ca84e44ccee698",
             ("short_k", "drift", "grad"): "c77c599162310cdd",
             ("short_k", "drift", "pgrad"): "a4fc96be761cbe1a",
             ("full_block", "normal", "fwd"): "4570d0d5c14e93d6",
             ("full_block", "normal", "grad"): "3432a2a4b30e94cc",
             ("full_block", "normal", "pgrad"): "9a1a8d1c6a597b37",
             ("full_block", "drift", "fwd"): "db9f0cd6169598d5",
             ("full_block", "drift", "grad"): "d733fe42de5025ed",
             ("full_block", "drift", "pgrad"): "bb4fc24e8b7a92f4"}


@pytest.mark.parametrize("problem,dist_id,mode", sorted(_UNPACKED))
def test_pack_one_is_the_unpacked_launch(problem, dist_id, mode):
    F, K, bf = _PROBLEMS[problem]
    assert autotune.pack_factor(bf, K) == 1
    T = 16
    rng = np.random.default_rng(20261018)
    mus = rng.uniform(5, 20, (F, K)).astype(np.float32)
    sgs = (mus * rng.uniform(0.05, 0.5, (F, K))).astype(np.float32)
    e = rng.exponential(size=(F, K))
    W = (e / e.sum(1, keepdims=True)).astype(np.float32)
    ex = rng.uniform(0, 0.3, (1, F, K)).astype(np.float32)
    outs = _launch(mode, W, mus, sgs, ex, dist_id, T, bf)
    h = hashlib.sha256()
    for o in outs:
        h.update(np.asarray(o, np.float32).tobytes())
    assert h.hexdigest()[:16] == _UNPACKED[(problem, dist_id, mode)]


def test_pack_factor_rule():
    # a block's lanes are 128 * ceil(bf / 128); slots walk >= 8 channels
    assert autotune.pack_factor(33, 6172) == 3      # Montage presolve
    assert autotune.pack_factor(11, 6172) == 11     # Montage refine
    assert autotune.pack_factor(45, 329) == 2       # Epigenomics refine
    assert autotune.pack_factor(135, 329) == 1      # 256 lanes, 270 > 256
    assert autotune.pack_factor(66, 6172) == 1
    assert autotune.pack_factor(128, 6172) == 1
    assert autotune.pack_factor(32, 4096) == 4
    assert autotune.pack_factor(8, 20) == 2         # ceil(20 / 3) < 8
    assert autotune.pack_factor(8, 6) == 1          # too short to split
    assert autotune.pack_factor(1, 10**6) == 128
    for bf in range(1, 300):
        for K in (1, 7, 8, 15, 16, 64, 329, 6172):
            c = autotune.pack_factor(bf, K)
            assert c >= 1 and bf * c <= 128 * -(-bf // 128)
            assert c == 1 or -(-K // c) >= 8


def _pallas_operand_shapes(fn, *args):
    """The operand shapes of every ``pallas_call`` that ``fn`` traces."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(v.aval.shape for v in eqn.invars))
            for p in eqn.params.values():
                for q in p if isinstance(p, (tuple, list)) else (p,):
                    q = getattr(q, "jaxpr", q)
                    if hasattr(q, "eqns"):
                        walk(q)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("fused", [False, True])
def test_ops_launches_packed_where_the_shapes_say(fused):
    """9 stacked rows of 40 channels through ``ops`` pack 5 slots a row: the
    kernel's W tile is (8, 45), one program of 8 channels on 45 lanes, and
    the result is the oracle's."""
    F, K, T = 9, 40, 32
    W, mus, sgs, extra = _problem("normal", F, K, seed=5, per_row=True)
    if fused:
        call = lambda *a: ops.frontier_moments_with_grads(
            *a, num_t=T, impl="pallas_interpret")
        want = ref.frontier_grid_with_grads_ref(W, mus, sgs, num_t=T,
                                                extra=extra)
    else:
        call = lambda *a: ops.frontier_moments(*a, num_t=T,
                                               impl="pallas_interpret")
        want = ref.frontier_grid_ref(W, mus, sgs, num_t=T, extra=extra)
    assert autotune.pack_factor(F, K) == 5
    shapes = _pallas_operand_shapes(call, W, mus, sgs)
    assert [s[0] for s in shapes] == [(1, 8, 45)]
    out = call(W, mus, sgs)
    for a, b in zip(out, want):
        _close(a, b, "ops packed launch")
    assert np.asarray(out[0]).shape == (F,)


# Montage 8 degrees (bench/configs/montage.json): 11 stages, the widest of
# 6,172 channels, solved with restarts=1 (3 starts), num_t 256, the coarse
# rung at 128 points and the final score at 2,048
MONTAGE_K, MONTAGE_S, MONTAGE_R = 6172, 11, 3


def _montage_rungs():
    """(rung, rows, num_t) of every launch a Montage solve can make, for
    1 to 3 triage survivors."""
    R, S = MONTAGE_R, MONTAGE_S
    yield "presolve", R * S, 128
    yield "triage", 2 * R * S, 128
    for s in range(1, R + 1):
        yield f"refine{s}", s * S, 256
        yield f"final_score{s}", 3 * s * S, 2048
        yield f"fragility{s}", 3 * s * S, 256
    yield "fragility_winner", S, 256


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("rung,rows,num_t", list(_montage_rungs()))
def test_every_montage_rung_fits_vmem_packed(rung, rows, num_t, mode):
    """The block the model picks for each rung's rows, in every mode, fits
    the scoped-VMEM budget with the pack the launch will use."""
    fused, params = mode != "fwd", mode == "pgrad"
    bf = autotune.pick_block_f(rows, MONTAGE_K, num_t, "pallas", fused=fused,
                               params=params)
    assert autotune.vmem_bytes(bf, MONTAGE_K, num_t, fused=fused,
                               params=params) <= autotune._VMEM_BUDGET_BYTES


def test_montage_pgrad_needs_packing():
    """Unpacked (a block that fills its lanes, pack 1), the full-parameter
    launch at 6,172 channels overflows the budget; the refine's 11 rows,
    packed 11 slots a row, fit with room."""
    assert autotune.pack_factor(128, MONTAGE_K) == 1
    assert autotune.vmem_bytes(128, MONTAGE_K, 256, fused=True,
                               params=True) > autotune._VMEM_BUDGET_BYTES
    assert autotune.vmem_bytes(11, MONTAGE_K, 256, fused=True,
                               params=True) < autotune._VMEM_BUDGET_BYTES / 4
