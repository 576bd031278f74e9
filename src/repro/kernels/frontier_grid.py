"""The paper's hot loop as a Pallas TPU kernel: survival-integral moments for a
grid of candidate splits, with an optional fused analytic-gradient pass —
generalized over pluggable completion-time families (normal / lognormal /
drift / empirical / defective, selected by a **static** ``dist_id`` so every
family compiles to its own specialized kernel).

Why a kernel: at fleet scale the scheduler re-evaluates mu(w), sigma^2(w) for
thousands of candidate splits x hundreds/thousands of channels every rebalance
tick (posteriors move every step). That is a dense (F x T x K) computation of
erf/exp/log with two reductions — VPU-bound, and exactly the kind of loop worth
tiling into VMEM instead of bouncing (F, T, K) intermediates through HBM.

Tiling: the candidate axis F is blocked (block_f rows per program) and laid
out channel-major: every per-channel array enters a program as a (K, block_f)
tile — channels on sublanes, candidates on lanes — and the survival
accumulator is a (T, block_f) tile with time on sublanes. The K channels
stream through a fori_loop that reads channel ``kk`` as the (1, block_f) row
``ref[pl.ds(kk, 1), :]`` (a dynamic sublane offset, which Mosaic lowers to
one load; a dynamic lane offset into a (block_f, K) tile would not lower)
and adds its log-CDF. Shared channel statistics arrive as one (K, block_f)
block that every program reads the same way. Reductions over T are sublane reductions, and the moments leave as
lane-dense (1, block_f) rows. The fused gradient kernel writes each
channel's accumulator row into (K, block_f) VMEM scratch — two for the
scale-like families, FOUR for ``drift`` (see the derivation below) — and its
epilogue walks K in 8-row sublane chunks, so no (K, block_f) temporary is
ever live. ``kernels.autotune.vmem_bytes`` models every buffer of that
layout (double-buffered blocks, scratch, (T, block_f) work tiles) and the
launch passes the same budget to the compiler as its scoped-VMEM limit.

Lane packing: a block of ``block_f`` rows fills 128 * ceil(block_f / 128)
lanes whatever block_f is, and a launch walks its K channels one per step,
so a launch of few rows leaves lanes empty for K serial steps. Where its
shapes give a ``pack`` c > 1 (``kernels.autotune.pack_factor`` of block_f
and K), each row's K channels are dealt over c lane slots: slot j of row
r is lane r + j * block_f and walks channels j * Kc .. (j + 1) * Kc - 1, Kc = ceil(K / c),
the channels that pad K to c * Kc being ``w = 0`` point masses (exact, as
below). The tiles are then (Kc, c * block_f). Each slot's partial log F(t)
and reach maximum are combined across the row's slots once per launch by
lane rolls (an add and a max, in float32 on the VPU, gathered onto slot 0
in slot order and copied back, so every slot of a row holds the same
bits); the gradient pass walks each slot's own channels against the row's
full F(t), and its moving-grid sums and argmax-tie count are combined the
same way. ``pack == 1`` is the unpacked layout above, op for op.

Per-candidate integration grids (t in [0, tmax_f]) keep accuracy uniform
across candidates whose means differ by orders of magnitude; ``tmax`` uses the
family's *effective* moments, max_k(mean_k(w) + z std_k(w)).

Differentiating the family-parametric survival integral
-------------------------------------------------------

The kernel computes, per candidate row w (weights over K channels with
per-unit-work statistics mu_k, sigma_k and family shape parameters
``extra[:, k]``):

    F(t)   = prod_k C_k(t; w_k)                 joint CDF of the max
    mu     = int_0^tmax (1 - F(t)) dt           survival-integral mean
    m2     = 2 int_0^tmax t (1 - F(t)) dt       second moment
    var    = m2 - mu^2

discretized by trapezoid quadrature on t_j = tmax * j/(T-1). For the Normal
family C_k(t) = Phi((t - w mu_k)/(w sigma_k)); the other families substitute
their own CDF (see ``core.distributions``). The adjoints stay a streaming
two-pass computation for EVERY family because each family's log-CDF
derivatives are affine in t after factoring out a pdf-like numerator D_k(t):

    d log C_k / d w_k |_t = g_jk * (alpha_k + beta_k t),
    d log C_k / d t   |_t = g_jk * (gamma0_k + gamma1_k t) / t,
    g_jk = gate_jk * D_k(t_j) / C_k(t_j)        (inverse-Mills-style ratio)

with per-channel constants (family_coeffs):

    normal      alpha=0,              beta=-1/(w^2 sigma),  gamma1=1/(w sigma)
    lognormal   alpha=-1/(w s_l),     beta=0,               gamma0=1/s_l
    drift       alpha=-rho mu/(2 s),  beta=-1/(w^2 sigma),  gamma1=1/(w sigma)
    empirical   alpha=0,              beta=-1/w^2,          gamma1=1/w
    defective   alpha=0,              beta=-1/(w^2 b),      gamma1=1/(w b)

(defective is the normal family with the retry-inflated moments (a, b)
substituted for (mu, sigma) — a pure scale family in w; see
``distributions._defective_ab``.)

(lognormal's z-score lives in log-space, so its dw-derivative is t-free;
drift's z = (t - mu g(w))/(w sigma) with g = w(1 + rho w/2) contributes both
a t-free and a t-linear term — that family alone needs all four
accumulators.) With a_jk = omega_j F(t_j) g_jk (omega_j trapezoid weights)
the fixed-grid adjoints contract into per-channel sums

    P0_k  = sum_j a_jk              Pv0_k = sum_j a_jk (t_j - mu)
    P1_k  = sum_j a_jk t_j          Pv1_k = sum_j a_jk t_j (t_j - mu)

    dmu/dw_k  (fixed grid) = -dt (alpha_k P0_k + beta_k P1_k)
    dvar/dw_k (fixed grid) = -2 dt (alpha_k Pv0_k + beta_k Pv1_k)

Parameter adjoints (the closed estimation loop)
-----------------------------------------------

The channel statistics are learned online, so the solve must also be
differentiable in mu_k, sigma_k and the family extras (drift's rho_k,
defective's failure probability p_k). The SAME contraction covers them: for any per-channel parameter theta_k,

    d log C_k / d theta_k |_t = g_jk * (a_k + b_k t + c_k z_jk)

is affine in the widened feature basis {1, t, z} (family_param_coeffs):

    normal      dz/dmu = -1/sigma                          {1}
                dz/dsigma = mu/sigma^2 - t/(w sigma^2)     {1, t}
    lognormal   dz/dtheta = -(dbase/dtheta)/s_l
                            - z (ds_l/dtheta)/s_l          {1, z}
    drift       dz/dmu = -g(w)/(w sigma)                   {1}
                dz/dsigma = mu g/(w sigma^2) - t/(w s^2)   {1, t}
                dz/drho = -mu w/(2 sigma)                  {1}
    empirical   (mus/sigmas unused; mixture extras are solve constants)
    defective   dz/dtheta = -(da/dtheta)/b
                            - z (db/dtheta)/b              {1, z}
                (theta in {mu, sigma, p}; lam is a pricing
                constant with documented-zero cotangent)

The z feature belongs to the families whose *spread* moves with the
statistics: lognormal's moment-matched shape s_l(mu, sigma) and defective's
composite b(mu, sigma, p), so dz/dmu picks up a term proportional to z
itself — which contracts against two more accumulators

    Pz_k  = sum_j a_jk z_jk         Pvz_k = sum_j a_jk z_jk (t_j - mu)

    dmu/dtheta_k  (fixed grid) = -dt (a_k P0 + b_k P1 + c_k Pz)_k
    dvar/dtheta_k (fixed grid) = -2 dt (a_k Pv0 + b_k Pv1 + c_k Pvz)_k

and every parameter also carries the moving-grid term below with
dtmax/dtheta_a = dreach_a/dtheta (family_dreach_params: w for mu, z_span*w
for sigma, mu w^2/2 for rho) on the argmax channel. So full-parameter mode
(static ``param_grads=True``) is the same two-pass streaming kernel with at
most SIX per-channel accumulators instead of four, six extra (K, block_f)
output tiles, and an unchanged K-loop count — the accumulators are shared
across w/mu/sigma/rho; only the epilogue contractions differ. The
``empirical`` family's mixture parameters are deliberately NOT adjointed
(re-fit from data each tick, never descended); its mus/sigmas cotangents
are exactly zero because the mixture CDF never reads them.

The Pv* accumulators fold the m2 and -2 mu dmu cotangents together per grid
point — the same combination autodiff's backward makes — which avoids the
catastrophic cancellation of accumulating them separately when var << mu^2.

Because the grid itself moves with w (t_j = tmax(w) * j/(T-1), dt ∝ tmax),
each output also carries a tmax term on the argmax channel
a = argmax_k(mean_k + z std_k), where dtmax/dw_a = dreach_a (family_dreach;
mu_a + z sigma_a for the normal/lognormal families):

    dmu/dtmax  = mu/tmax  - (dt/tmax)  sum_k (gamma0_k P0_k + gamma1_k P1_k)
    dvar/dtmax = 2 var/tmax
                 - (2 dt/tmax) sum_k (gamma0_k Pv0_k + gamma1_k Pv1_k)

(The continuum limit of dmu/dtmax is surv(tmax) ~ 0 at z=10; these discrete
forms keep exact parity with autodiff through the quadrature.) Degenerate
point-mass channels (w=0, sigma=0, spread-free mixtures) contribute no direct
term (their CDF — right-continuous per ``distributions.point_mass_cdf`` — is
flat a.e.) but still receive the tmax term when they set the grid end; CDF
values clipped to the [1e-37, 1] floor/ceiling follow jnp.clip's gradient
conventions (0 below the floor, 0.5 exactly at saturation).

The fused kernel computes the forward pass (one K-loop building log F), then a
second K-loop accumulating the P*/Pv* sums per channel from the shared
(T, block_f) joint-CDF tile — so ``(mu, var, dmu_dW, dvar_dW)`` costs ~2
forward passes in one launch, instead of a forward plus a full autodiff
replay through the quadrature graph.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["frontier_grid", "frontier_grid_with_grads"]

from . import autotune as _at
from .ref import _CDF_FLOOR  # single source: kernel must match its oracle
from repro.core import distributions as dists


def _nearest_valid_block_f(F: int, block_f: int) -> int:
    """The divisor of F closest to the requested block_f (ties go smaller:
    a smaller tile always fits where the larger one would have)."""
    divisors = [d for d in range(1, F + 1) if F % d == 0]
    return min(divisors, key=lambda d: (abs(d - block_f), d))


def _check_block(F: int, K: int, block_f: int, dist_id: str,
                 mode: str) -> None:
    # a real error, not an assert: asserts vanish under python -O and callers
    # outside ops.py would get a silent wrong-shape launch
    if F % block_f:
        raise ValueError(
            f"launch shape invalid: F={F} not divisible by block_f={block_f} "
            f"(K={K}, dist_id={dist_id!r}, mode={mode!r}); nearest valid "
            f"block_f is {_nearest_valid_block_f(F, block_f)}. "
            f"ops.frontier_moments pads W with copies of row 0 to guarantee "
            f"divisibility — call through it, or pass a block_f dividing F.")


def _channel_rows(refs, sl):
    """(w, mus, sigmas, extra) at channel rows ``sl``, each (rows, bf);
    ``extra`` as a tuple of its E rows, which the family math indexes like
    the array (and no 3-D value reaches Mosaic)."""
    w_ref, mu_ref, sg_ref, ex_ref = refs
    return (w_ref[sl, :], mu_ref[sl, :], sg_ref[sl, :],
            tuple(ex_ref[e, sl, :] for e in range(ex_ref.shape[0])))


def _fold_channels(num_k: int, body, carry):
    """Fold ``body(sl, carry)`` over the channels in 8-row sublane chunks at
    aligned dynamic offsets, then over the static remainder rows."""
    sub = _at.SUBLANES   # the channel-chunk height of the K folds
    n = num_k // sub

    def chunk(c, acc):
        return body(pl.ds(pl.multiple_of(c * sub, sub), sub), acc)

    if n:
        carry = jax.lax.fori_loop(0, n, chunk, carry)
    if num_k % sub:
        carry = body(pl.ds(n * sub, num_k % sub), carry)
    return carry


def _combine_slots(x, pack: int, op):
    """Each row's ``pack`` lane slots of ``x`` (.., pack * bf) combined by
    ``op``, the result in every slot of the row.

    Slot j of row r is lane r + j * bf. The slots are gathered onto slot 0
    in slot order (lane r takes lane r + j * bf by a roll), and slot 0's
    result is rolled back into each other slot, so all slots of a row hold
    the same bits. ``pack == 1`` returns ``x`` itself.
    """
    if pack == 1:
        return x
    lanes = x.shape[-1]
    bf = lanes // pack
    acc = x
    for j in range(1, pack):
        acc = op(acc, pltpu.roll(x, lanes - j * bf, axis=x.ndim - 1))
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    out = acc
    for j in range(1, pack):
        out = jnp.where(lane >= j * bf,
                        pltpu.roll(acc, j * bf, axis=x.ndim - 1), out)
    return out


def _prologue(refs, num_t: int, z: float, dist_id: str, pack: int,
              reach_ref=None):
    """Grid end, time grid and trapezoid weights shared by both kernels.

    Returns ``(amax, tmax, ts, wq)``: the unclamped per-candidate grid end
    (1, bf), its clamp, the (T, bf) time grid and the (T, 1) trapezoid
    weights (end points folded in at 0.5). ``reach_ref`` keeps every
    channel's reach for the adjoint's argmax ties, so the epilogue compares
    the very values the max was taken over. With ``pack`` slots a row, the
    grid end is the maximum over all of the row's slots.
    """
    num_k, block_f = refs[0].shape

    def reach_max(sl, m):
        me, se = dists.family_effective_moments(
            dist_id, *_channel_rows(refs, sl))
        reach = me + z * se
        if reach_ref is not None:
            # max over the stored rows: a compiler may evaluate `reach` once
            # per consumer with different rounding, and a tie must survive
            reach_ref[sl, :] = reach
            reach = reach_ref[sl, :]
        return jnp.maximum(m, jnp.max(reach, axis=0, keepdims=True))

    amax = _fold_channels(num_k, reach_max,
                          jnp.full((1, block_f), -jnp.inf, jnp.float32))
    amax = _combine_slots(amax, pack, jnp.maximum)
    tmax = jnp.maximum(amax, 1e-12)
    idx = jax.lax.broadcasted_iota(jnp.int32, (num_t, 1), 0)
    ts = tmax * (idx.astype(jnp.float32) / (num_t - 1))       # (T, bf)
    wq = jnp.where((idx == 0) | (idx == num_t - 1), 0.5, 1.0)
    return amax, tmax, ts, wq


def _log_joint_cdf(refs, ts, dist_id: str, pack: int):
    """log F(t) on the (T, bf) grid: one channel per fori_loop step, each
    row's slots summed once at the end."""
    def add_channel(kk, logF):
        cdf = dists.family_cdf(dist_id, ts,
                               *_channel_rows(refs, pl.ds(kk, 1)))
        return logF + jnp.log(jnp.clip(cdf, _CDF_FLOOR, 1.0))

    logF = jax.lax.fori_loop(0, refs[0].shape[0], add_channel,
                             jnp.zeros_like(ts))
    return _combine_slots(logF, pack, jnp.add)


def _frontier_kernel(w_ref, mu_ref, sg_ref, ex_ref, mu_out_ref, var_out_ref, *,
                     num_t: int, z: float, dist_id: str, pack: int):
    refs = (w_ref, mu_ref, sg_ref, ex_ref)   # (K, bf) tiles, extra (E, K, bf)
    _, tmax, ts, wq = _prologue(refs, num_t, z, dist_id, pack)
    surv = 1.0 - jnp.exp(_log_joint_cdf(refs, ts, dist_id, pack))  # (T, bf)
    dt = tmax / (num_t - 1)
    mu = jnp.sum(wq * surv, axis=0, keepdims=True) * dt
    m2 = 2.0 * jnp.sum(wq * ts * surv, axis=0, keepdims=True) * dt
    mu_out_ref[...] = mu
    var_out_ref[...] = jnp.maximum(m2 - mu * mu, 0.0)


def _family_extra(dist_id: str, extra, K: int, F=None):
    """Validated (E, K) extra, or (E, F, K) when statistics are per-row."""
    E = dists.extra_rows(dist_id)
    if extra is None:
        extra = jnp.zeros((E, K) if F is None else (E, F, K), jnp.float32)
    extra = jnp.asarray(extra, jnp.float32)
    want = (E, K) if F is None else (E, F, K)
    if extra.shape != want:
        raise ValueError(f"extra for {dist_id!r} must be {want}, "
                         f"got {extra.shape}")
    return extra


def _pad_channels(a, kc: int, pack: int, mode: str):
    """``a`` (..., K) with its last axis padded to pack * kc channels: zero
    weights (``constant``) or copies of the last channel's statistics
    (``edge``), so a padding channel is a w = 0 point mass."""
    pad = pack * kc - a.shape[-1]
    if not pad:
        return a
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)], mode=mode)


def _launch_operands(W, mus, sigmas, extra, dist_id: str, block_f: int,
                     pack: int):
    """Channel-major kernel operands and their BlockSpecs.

    (F, K) arrays become (G, K, block_f) with G = F // block_f programs, so
    each program's tile is (K, block_f) whatever block_f is. Shared stats
    are broadcast across block_f lanes once, here, and every program reads
    that one block: Mosaic cannot broadcast a (1, 1) value into a (T, bf)
    tile, so a stat must already be a (1, bf) row when the kernel loads it.
    Per-row stats tile along F exactly like W.

    With ``pack`` c > 1 the tiles are (Kc, c * block_f), Kc = ceil(K / c):
    channel j * Kc + i of row r sits at sublane i of lane r + j * block_f.
    """
    F, K = W.shape
    G = F // block_f
    kc = -(-K // pack)
    lane_n = pack * block_f

    def channel_major(a):  # (..., F, K) -> (G, ..., kc, lane_n)
        lead = a.shape[:-2]
        a = a.reshape(lead + (G, block_f, pack, kc))
        a = jnp.moveaxis(a, len(lead), 0)
        a = jnp.swapaxes(a, -1, -3)             # (G, ..., kc, pack, bf)
        return a.reshape(a.shape[:-3] + (kc, lane_n))

    def lanes(a):  # (..., K) -> (..., kc, lane_n)
        a = jnp.swapaxes(a.reshape(a.shape[:-1] + (pack, kc)), -1, -2)
        a = jnp.broadcast_to(a[..., None], a.shape + (block_f,))
        return a.reshape(a.shape[:-3] + (kc, lane_n))

    W = W.astype(jnp.float32)
    mus = jnp.asarray(mus, jnp.float32)
    sgs = jnp.asarray(sigmas, jnp.float32)
    per_row = mus.ndim == 2
    ex = _family_extra(dist_id, extra, K, F if per_row else None)
    E = ex.shape[0]
    W = _pad_channels(W, kc, pack, "constant")
    mus, sgs, ex = (_pad_channels(a, kc, pack, "edge")
                    for a in (mus, sgs, ex))
    tile = pl.BlockSpec((None, kc, lane_n), lambda i: (i, 0, 0))
    if per_row:
        operands = tuple(channel_major(a) for a in (W, mus, sgs, ex))
        specs = [tile, tile, tile,
                 pl.BlockSpec((None, E, kc, lane_n), lambda i: (i, 0, 0, 0))]
    else:
        operands = (channel_major(W), lanes(mus), lanes(sgs), lanes(ex))
        shared = pl.BlockSpec((kc, lane_n), lambda i: (0, 0))
        specs = [tile, shared, shared,
                 pl.BlockSpec((E, kc, lane_n), lambda i: (0, 0, 0))]
    return operands, specs


def _moment_outputs(G: int, lane_n: int):
    """(specs, shapes) of the two lane-dense (G, 1, lane_n) moment rows."""
    spec = pl.BlockSpec((None, 1, lane_n), lambda i: (i, 0, 0))
    shape = jax.ShapeDtypeStruct((G, 1, lane_n), jnp.float32)
    return [spec, spec], [shape, shape]


def _row_moments(o, F: int, block_f: int, pack: int):
    """(F,) moments from a (G, 1, pack * block_f) output: slot 0's lanes."""
    return o.reshape(-1, pack * block_f)[:, :block_f].reshape(F)


def _row_channels(o, F: int, K: int, block_f: int, pack: int):
    """(F, K) per-channel outputs from (G, Kc, pack * block_f) tiles."""
    G, kc, _ = o.shape
    o = jnp.transpose(o.reshape(G, kc, pack, block_f), (0, 3, 2, 1))
    return o.reshape(F, pack * kc)[:, :K]


# the scoped-VMEM limit the autotune model budgets against: every block the
# model picks fits it by construction
_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=_at._VMEM_BUDGET_BYTES)


@functools.partial(jax.jit, static_argnames=("num_t", "z", "block_f",
                                             "interpret", "dist_id"))
def frontier_grid(W, mus, sigmas, extra=None, *, num_t: int = 1024,
                  z: float = 10.0, block_f: int = 128,
                  interpret: bool = False, dist_id: str = "normal"):
    """(mu, var) arrays of shape (F,) for candidate splits W: (F, K).

    ``dist_id`` statically selects the completion-time family; ``extra`` is
    its (E, K) per-channel shape-parameter array (zeros when the family has
    none). ``mus``/``sigmas`` may also be (F, K) — per-row channel
    statistics, the stage-stacked layout where every candidate row carries
    its own fleet (``extra`` then (E, F, K)); the stat tiles ride the same
    F-blocking as W instead of broadcasting one tile to every program. F
    must be divisible by block_f (ops.py pads with copies of row 0
    otherwise). A block of few rows deals each row's channels over the
    lanes it would leave empty (module docstring, "Lane packing").
    """
    F, K = W.shape
    block_f = min(block_f, F)
    pack = _at.pack_factor(block_f, K)
    _check_block(F, K, block_f, dist_id, "fwd")
    operands, in_specs = _launch_operands(W, mus, sigmas, extra, dist_id,
                                          block_f, pack)
    G = F // block_f
    out_specs, out_shape = _moment_outputs(G, pack * block_f)
    kernel = functools.partial(_frontier_kernel, num_t=num_t, z=z,
                               dist_id=dist_id, pack=pack)
    mu, var = pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=f"frontier_grid_fwd_{dist_id}",
    )(*operands)
    return (_row_moments(mu, F, block_f, pack),
            _row_moments(var, F, block_f, pack))


def _frontier_grad_kernel(w_ref, mu_ref, sg_ref, ex_ref,
                          mu_out_ref, var_out_ref, dmu_out_ref, dvar_out_ref,
                          *rest, num_t: int, z: float, dist_id: str,
                          param_grads: bool, pack: int):
    """Fused forward + analytic adjoint (see module docstring for the math).

    Pass 1 is the forward K-loop building the joint log-CDF; pass 2 streams K
    again, turning the shared (T, bf) joint-CDF tile into the per-channel
    P*/Pv* accumulator rows — one pair per live feature in
    ``distributions.family_features(dist_id, param_grads)``, so unused
    accumulators never exist in the compiled program. The accumulators are
    (K, bf) VMEM scratch written one channel row per step — no (F, T, K)
    residuals ever leave the program. The epilogue then walks K twice in
    8-row chunks: once for the moving-grid sums over channels, once to
    contract the accumulators into the output rows. With ``param_grads``
    the same passes additionally emit the mus/sigmas/extra-row-0 adjoints
    (six more (K, bf) outputs): the parameter cotangents contract the SAME
    accumulators against different per-channel constants, so
    full-parameter mode costs extra epilogue arithmetic and output tiles,
    not a third K-loop. With ``pack`` slots a row, each slot walks its own
    channels against the row's combined F(t); the moving-grid sums and
    the argmax-tie count are summed over the row's slots before use.
    """
    n_param_outs = 6 if param_grads else 0
    out_refs = (dmu_out_ref, dvar_out_ref) + tuple(rest[:n_param_outs])
    reach_ref, *acc_refs = rest[n_param_outs:]
    refs = (w_ref, mu_ref, sg_ref, ex_ref)
    amax, tmax, ts, wq = _prologue(refs, num_t, z, dist_id, pack, reach_ref)
    F_t = jnp.exp(_log_joint_cdf(refs, ts, dist_id, pack))
    surv = 1.0 - F_t

    dt = tmax / (num_t - 1)                                   # (1, bf)
    mu = jnp.sum(wq * surv, axis=0, keepdims=True) * dt
    m2 = 2.0 * jnp.sum(wq * ts * surv, axis=0, keepdims=True) * dt
    var_raw = m2 - mu * mu
    mu_out_ref[...] = mu
    var_out_ref[...] = jnp.maximum(var_raw, 0.0)

    # pass 2: per-channel accumulators off the shared F(t) tile. wF folds the
    # trapezoid weights into the joint CDF once.
    wF = wq * F_t                                             # (T, bf)
    tmu = ts - mu                                             # (T, bf)
    feats = [f for f, use in zip("1tz", dists.family_features(
        dist_id, params=param_grads)) if use]

    def grad_channel(kk, carry):
        sl = pl.ds(kk, 1)
        cdf_raw, D, ok, zsc = dists.family_adjoint_parts(
            dist_id, ts, *_channel_rows(refs, sl))
        Cc = jnp.clip(cdf_raw, _CDF_FLOOR, 1.0)
        gate = jnp.where(cdf_raw >= 1.0, 0.5, 1.0) * (cdf_raw > _CDF_FLOOR) * ok
        a = wF * (gate * D / Cc)                              # (T, bf)
        basis = {"1": a, "t": a * ts, "z": a * zsc}
        for i, f in enumerate(feats):
            acc_refs[2 * i][sl, :] = jnp.sum(basis[f], axis=0, keepdims=True)
            acc_refs[2 * i + 1][sl, :] = jnp.sum(basis[f] * tmu, axis=0,
                                                 keepdims=True)
        return carry

    jax.lax.fori_loop(0, w_ref.shape[0], grad_channel, None)

    def accs(sl):
        """{feature: (P_f, Pv_f)} rows; absent features contract as 0."""
        P = dict.fromkeys("1tz", (0.0, 0.0))
        for i, f in enumerate(feats):
            P[f] = (acc_refs[2 * i][sl, :], acc_refs[2 * i + 1][sl, :])
        return P

    # epilogue: combine fixed-grid and moving-grid (tmax) terms with the
    # family's per-channel constants — module docstring "Differentiating the
    # family-parametric survival integral"
    def grid_sums(sl, carry):
        s_mu, s_var, n_tie = carry
        _, _, gamma0, gamma1 = dists.family_coeffs(
            dist_id, *_channel_rows(refs, sl))
        P = accs(sl)
        s_mu = s_mu + jnp.sum(gamma0 * P["1"][0] + gamma1 * P["t"][0],
                              axis=0, keepdims=True)
        s_var = s_var + jnp.sum(gamma0 * P["1"][1] + gamma1 * P["t"][1],
                                axis=0, keepdims=True)
        n_tie = n_tie + jnp.sum((reach_ref[sl, :] == amax).astype(jnp.float32),
                                axis=0, keepdims=True)
        return s_mu, s_var, n_tie

    zero_row = jnp.zeros_like(amax)
    s_mu, s_var, n_tie = (
        _combine_slots(x, pack, jnp.add)
        for x in _fold_channels(w_ref.shape[0], grid_sums, (zero_row,) * 3))
    b_mu = (mu - dt * s_mu) / tmax
    b_var = 2.0 * (var_raw - dt * s_var) / tmax
    # ties split the tmax cotangent evenly (n_tie >= 1: amax is one of them)
    tie_w = (amax > 1e-12).astype(jnp.float32) / n_tie
    var_pos = var_raw > 0.0

    def emit(sl, carry):
        rows = _channel_rows(refs, sl)
        P = accs(sl)
        tie = (reach_ref[sl, :] == amax).astype(jnp.float32) * tie_w

        def contract(c1, ct, cz, dreach):
            gvec = dreach * tie
            dmu_th = (-dt * (c1 * P["1"][0] + ct * P["t"][0] + cz * P["z"][0])
                      + b_mu * gvec)
            dvar_th = jnp.where(
                var_pos,
                -2.0 * dt * (c1 * P["1"][1] + ct * P["t"][1]
                             + cz * P["z"][1])
                + b_var * gvec, 0.0)
            return dmu_th, dvar_th

        alpha, beta, _, _ = dists.family_coeffs(dist_id, *rows)
        grads = [contract(alpha, beta, 0.0,
                          dists.family_dreach(dist_id, *rows, z))]
        if param_grads:
            c_mu, c_sigma, c_rho = dists.family_param_coeffs(dist_id, *rows)
            dr_mu, dr_sigma, dr_rho = dists.family_dreach_params(
                dist_id, *rows, z)
            grads += [contract(*c_mu, dr_mu), contract(*c_sigma, dr_sigma),
                      contract(*c_rho, dr_rho)
                      if dists.family_has_extra_grads(dist_id)
                      else (0.0, 0.0)]
        for ref, g in zip(out_refs, (g for pair in grads for g in pair)):
            ref[sl, :] = jnp.broadcast_to(g, tie.shape)
        return carry

    _fold_channels(w_ref.shape[0], emit, None)


@functools.partial(jax.jit, static_argnames=("num_t", "z", "block_f",
                                             "interpret", "dist_id",
                                             "param_grads"))
def frontier_grid_with_grads(W, mus, sigmas, extra=None, *, num_t: int = 1024,
                             z: float = 10.0, block_f: int = 64,
                             interpret: bool = False,
                             dist_id: str = "normal",
                             param_grads: bool = False):
    """Fused ``(mu, var, dmu_dW, dvar_dW)`` for candidate splits W: (F, K).

    One launch returns the moments AND their analytic adjoints w.r.t. every
    split weight (matching ``ref.frontier_grid_with_grads_ref``) for the
    family statically selected by ``dist_id``. With ``param_grads=True`` the
    same single launch additionally emits the channel-statistic adjoints —
    ``(dmu_dmus, dvar_dmus, dmu_dsigmas, dvar_dsigmas, dmu_dex, dvar_dex)``,
    all (F, K), ``d*_dex`` being extra row 0 (drift's rho, defective's p; zeros for
    families without differentiable extra) — the full-parameter mode the estimation
    loop's custom VJP rides. ``mus``/``sigmas`` may be (F, K) per-row
    statistics (``extra`` then (E, F, K)) exactly as in
    :func:`frontier_grid`; the adjoint outputs are per-row either way, so
    only the input tiling changes. F must be divisible by block_f (ops.py
    pads with copies of row 0 otherwise); lanes packed as in
    :func:`frontier_grid`.
    """
    F, K = W.shape
    block_f = min(block_f, F)
    pack = _at.pack_factor(block_f, K)
    _check_block(F, K, block_f, dist_id, "pgrad" if param_grads else "grad")
    operands, in_specs = _launch_operands(W, mus, sigmas, extra, dist_id,
                                          block_f, pack)
    G = F // block_f
    kc, lane_n = -(-K // pack), pack * block_f
    out_specs, out_shape = _moment_outputs(G, lane_n)
    n_fk_outs = 8 if param_grads else 2
    out_specs += [pl.BlockSpec((None, kc, lane_n), lambda i: (i, 0, 0))
                  ] * n_fk_outs
    out_shape += [jax.ShapeDtypeStruct((G, kc, lane_n), jnp.float32)
                  ] * n_fk_outs
    # the reach rows plus one (P_f, Pv_f) accumulator pair per live feature
    n_acc = 2 * sum(dists.family_features(dist_id, params=param_grads))
    scratch = [pltpu.VMEM((kc, lane_n), jnp.float32)] * (1 + n_acc)
    kernel = functools.partial(_frontier_grad_kernel, num_t=num_t, z=z,
                               dist_id=dist_id, param_grads=param_grads,
                               pack=pack)
    mode = "pgrad" if param_grads else "grad"
    mu, var, *fk = pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=f"frontier_grid_{mode}_{dist_id}",
    )(*operands)
    return (_row_moments(mu, F, block_f, pack),
            _row_moments(var, F, block_f, pack)) + tuple(
        _row_channels(o, F, K, block_f, pack) for o in fk)
