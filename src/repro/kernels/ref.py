"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics; the kernels must match them (asserted by
tests/test_kernels.py, test_families.py and test_lane_packing.py, kernels
run in interpret=True on CPU).

The frontier oracles are family-generic: every completion-time family in
``core.distributions.FAMILIES`` — normal, lognormal, drift, empirical,
defective — flows through the ``dists.family_*`` dispatch on the static
``dist_id``; there are no per-family branches in the quadrature itself.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["frontier_grid_ref", "frontier_grid_with_grads_ref"]

# log-CDF clamp floor. Must be a NORMAL f32 (>= 1.18e-38): XLA CPU flushes
# subnormals to zero, and a flushed floor turns the log/clip VJP into
# inf * 0 = NaN — the PGD solver differentiates through this function.
_CDF_FLOOR = 1e-37

_INV_SQRT2PI = 0.3989422804014327  # 1/sqrt(2*pi) (dists.phi's constant; kept
# exported — kernel-parity tests and external callers reference it)

# Constants above must precede this import: repro.core's init transitively
# re-imports this module (core.frontier -> kernels.ops -> frontier_grid ->
# ref._CDF_FLOOR), so the re-entrant import must find them already bound.
from repro.core import distributions as dists  # noqa: E402


def _family_args(dist_id, extra, K):
    if extra is None:
        extra = jnp.zeros((dists.extra_rows(dist_id), K), jnp.float32)
    return jnp.asarray(extra, jnp.float32)


# repro: allow[RPA001] layout-only axis alignment: family dispatch happens in
# the family_cdf call of the caller, which holds the static dist_id
def _stat_bcast(mus, sigmas, extra):
    """Broadcast shapes for the (F, T, K) grid calls.

    Shared statistics (``mus``/``sigmas`` (K,), ``extra`` (E, K)) broadcast
    against the (F, T, K) grid as-is. Per-row statistics — the stage-stacked
    layout where row f carries its own channel fleet: ``mus``/``sigmas``
    (F, K), ``extra`` (E, F, K) — need an explicit time axis inserted so the
    row axis lines up with F rather than T.
    """
    if mus.ndim == 2:
        return mus[:, None, :], sigmas[:, None, :], extra[:, :, None, :]
    return mus, sigmas, extra


def frontier_grid_ref(W, mus, sigmas, num_t: int = 1024, z: float = 10.0,
                      dist_id: str = "normal", extra=None):
    """(mu, var) of the joint max-completion time for each candidate split.

    W: (F, K) rows on the simplex; mus/sigmas: (K,) shared across rows, or
    (F, K) per-row (the stage-stacked layout: every candidate row carries its
    own channel fleet — what lets one launch serve a whole workflow DAG); the
    per-channel completion-time distribution is the family named by static
    ``dist_id`` with per-channel shape parameters ``extra`` ((E, K), or
    (E, F, K) per-row, see ``core.distributions``). Per-candidate integration
    grid [0, max_i(mean_i(w) + z*std_i(w))], num_t pts, on the family's
    effective moments. Mirrors repro.core.maxstat.max_moments_quad but with a
    per-row grid so the whole batch is one fused computation (the kernel's
    contract).
    """
    W = jnp.asarray(W, jnp.float32)
    mus = jnp.asarray(mus, jnp.float32)
    sigmas = jnp.asarray(sigmas, jnp.float32)
    extra = _family_args(dist_id, extra, W.shape[1])
    means_eff, stds_eff = dists.family_effective_moments(
        dist_id, W, mus, sigmas, extra)                          # (F, K)
    tmax = jnp.maximum(jnp.max(means_eff + z * stds_eff, axis=-1), 1e-12)
    ts = tmax[:, None] * jnp.linspace(0.0, 1.0, num_t)[None, :]  # (F, T)

    mus_b, sgs_b, ex_b = _stat_bcast(mus, sigmas, extra)
    cdf = dists.family_cdf(dist_id, ts[:, :, None], W[:, None, :],
                           mus_b, sgs_b, ex_b)                   # (F, T, K)
    logF = jnp.sum(jnp.log(jnp.clip(cdf, _CDF_FLOOR, 1.0)), axis=-1)  # (F, T)
    surv = 1.0 - jnp.exp(logF)

    dt = tmax / (num_t - 1)
    mu = (jnp.sum(surv, -1) - 0.5 * (surv[:, 0] + surv[:, -1])) * dt
    tsurv = ts * surv
    m2 = 2.0 * (jnp.sum(tsurv, -1) - 0.5 * (tsurv[:, 0] + tsurv[:, -1])) * dt
    var = jnp.maximum(m2 - mu * mu, 0.0)
    return mu, var


def frontier_grid_with_grads_ref(W, mus, sigmas, num_t: int = 1024,
                                 z: float = 10.0, dist_id: str = "normal",
                                 extra=None, param_grads: bool = False):
    """Fused oracle: ``(mu, var, dmu_dW, dvar_dW)`` for candidate splits W.

    Same forward contract as :func:`frontier_grid_ref` (family selected by
    static ``dist_id``; ``mus``/``sigmas``/``extra`` may be shared across
    rows or per-row exactly as there), plus the analytic adjoints of both
    moments w.r.t. every split weight, computed in the same pass — the
    semantics the fused Pallas kernel must match and the function the
    ``frontier_moments`` custom VJP rides. Per-row statistics change nothing
    in the adjoint math: every contraction is already per-row, the shared
    case was just broadcasting one fleet over all rows.

    With ``param_grads=True`` the adjoint basis widens to the full channel
    statistics and the return is the 10-tuple

        (mu, var, dmu_dW, dvar_dW, dmu_dmus, dvar_dmus,
         dmu_dsigmas, dvar_dsigmas, dmu_dex, dvar_dex)

    where ``dmu_dmus[f, k] = d mu_f / d mu_k`` etc. and ``d*_dex`` is the
    cotangent of ``extra`` **row 0** — drift's per-channel ``rho``, the
    defective family's failure probability ``p``; zero for every other
    family (the empirical mixture's fitted parameters, like defective's
    pricing constant ``lam`` in row 1, are solve constants by contract, see
    ``distributions.family_has_extra_grads``).
    This is the estimation-loop surface: the ``frontier_moments`` custom VJP
    and ``core.sensitivity`` ride these outputs to differentiate the solve
    through the posterior point estimates.

    The adjoint must agree with ``jax.grad`` through the quadrature graph, so
    it replicates autodiff's boundary conventions exactly:

    * ``jnp.clip(cdf, floor, 1)`` passes gradient 1 strictly inside the
      bounds, 0.5 at a saturated bound (f32 CDF hits exactly 1.0 for
      z >= ~5.3), and 0 outside. The lower clip activates only where the
      CDF falls below the floor (z < ~-12.7, ``dists.Phi`` keeps relative
      accuracy down there); an exact tie with the floor is measure-zero.
    * ``jnp.max`` over channels splits the tmax cotangent evenly over ties.
    * degenerate (point-mass) channels take the non-differentiable branch, so
      their direct gradient is 0 — they still receive the grid-path gradient
      when they set ``tmax``.

    The family enters through the affine decomposition
    ``dC/dtheta = D(t) (a + b t + c z)`` over the per-family feature basis of
    ``core.distributions.family_features`` (see ``frontier_grid.py`` for the
    derivation): the t-sums contract into at most six per-channel
    accumulators (P0/P1/Pz and their Pv* twins), of which each
    (family, param mode) pair statically needs a subset.
    """
    W = jnp.asarray(W, jnp.float32)
    mus = jnp.asarray(mus, jnp.float32)
    sigmas = jnp.asarray(sigmas, jnp.float32)
    extra = _family_args(dist_id, extra, W.shape[1])
    means_eff, stds_eff = dists.family_effective_moments(
        dist_id, W, mus, sigmas, extra)                      # (F, K)
    reach = means_eff + z * stds_eff
    amax = jnp.max(reach, axis=-1)        # (F,) unclamped grid end
    tmax = jnp.maximum(amax, 1e-12)
    ts = tmax[:, None] * jnp.linspace(0.0, 1.0, num_t)[None, :]  # (F, T)

    mus_b, sgs_b, ex_b = _stat_bcast(mus, sigmas, extra)
    cdf_raw, D, ok, zsc = dists.family_adjoint_parts(
        dist_id, ts[:, :, None], W[:, None, :], mus_b, sgs_b, ex_b)  # (F,T,K)
    cdf = jnp.where(ok, cdf_raw,
                    dists.point_mass_cdf(ts[:, :, None], means_eff[:, None, :]))
    Cc = jnp.clip(cdf, _CDF_FLOOR, 1.0)
    F_t = jnp.exp(jnp.sum(jnp.log(Cc), axis=-1))     # joint CDF (F, T)
    surv = 1.0 - F_t

    dt = tmax / (num_t - 1)
    wq = jnp.ones((num_t,), jnp.float32).at[0].set(0.5).at[-1].set(0.5)
    mu = jnp.sum(wq * surv, -1) * dt
    m2 = 2.0 * jnp.sum(wq * ts * surv, -1) * dt
    var_raw = m2 - mu * mu
    var = jnp.maximum(var_raw, 0.0)

    # d logF / d w_k |_t = gate * D/Cc * (alpha_k + beta_k t), gated by the
    # clip conventions (family-generic inverse-Mills ratio)
    gate = (jnp.where(cdf_raw >= 1.0, 0.5, 1.0)
            * (cdf_raw > _CDF_FLOOR) * ok)
    r = gate * D / Cc                                # (F, T, K)
    a = (wq[None, :, None] * F_t[:, :, None]) * r    # trapezoid-weighted
    use_1, use_t, use_z = dists.family_features(dist_id, params=param_grads)
    ones_t = jnp.ones_like(ts)
    # var accumulators combine the m2 and -2*mu*mu cotangents PER GRID POINT
    # (t_j - mu), exactly as autodiff's backward does — accumulating them
    # separately and subtracting after the reduction loses ~3 digits to
    # cancellation when var << mu^2
    tmu = ts - mu[:, None]
    P0 = jnp.einsum("ftk,ft->fk", a, ones_t) if use_1 else 0.0
    Pv0 = jnp.einsum("ftk,ft->fk", a, tmu) if use_1 else 0.0
    P1 = jnp.einsum("ftk,ft->fk", a, ts) if use_t else 0.0
    Pv1 = jnp.einsum("ftk,ft->fk", a, ts * tmu) if use_t else 0.0
    # the z feature rides inside the (F, T, K)-shaped a*z product (z varies
    # per channel), so its accumulators contract without the shared-t einsum
    Pz = jnp.sum(a * zsc, axis=1) if use_z else 0.0
    Pvz = jnp.sum(a * zsc * tmu[:, :, None], axis=1) if use_z else 0.0

    alpha, beta, gamma0, gamma1 = dists.family_coeffs(
        dist_id, W, mus, sigmas, extra)              # (F, K) each

    # grid terms: every z_jk moves with tmax, and dt scales with tmax, so
    # dmu/dtmax = mu/tmax - (dt/tmax) sum_k (gamma0 P0 + gamma1 P1)_k
    # and dvar/dtmax = 2 (var - dt sum_k (gamma0 Pv0 + gamma1 Pv1)_k) / tmax
    b_mu = (mu - dt * jnp.sum(gamma0 * P0 + gamma1 * P1, -1)) / tmax
    b_var = 2.0 * (var_raw
                   - dt * jnp.sum(gamma0 * Pv0 + gamma1 * Pv1, -1)) / tmax
    # dtmax/dtheta_k = dreach_k/dtheta on argmax channels (ties split evenly)
    ind = (reach == amax[:, None]).astype(jnp.float32)
    tie = ind / jnp.sum(ind, -1, keepdims=True) * (amax > 1e-12)[:, None]
    var_pos = (var_raw > 0.0)[:, None]

    def contract(coeff_1, coeff_t, coeff_z, dreach):
        """Fixed-grid + moving-grid adjoint for one parameter axis."""
        gvec = dreach * tie
        dmu_th = (-dt[:, None] * (coeff_1 * P0 + coeff_t * P1 + coeff_z * Pz)
                  + b_mu[:, None] * gvec)
        dvar_th = jnp.where(
            var_pos,
            -2.0 * dt[:, None] * (coeff_1 * Pv0 + coeff_t * Pv1
                                  + coeff_z * Pvz)
            + b_var[:, None] * gvec, 0.0)
        return dmu_th, dvar_th

    dreach_w = dists.family_dreach(dist_id, W, mus, sigmas, extra, z)
    zero_fk = jnp.zeros_like(W * mus)
    dmu, dvar = contract(alpha, beta, zero_fk, dreach_w)
    if not param_grads:
        return mu, var, dmu, dvar

    c_mu, c_sigma, c_rho = dists.family_param_coeffs(
        dist_id, W, mus, sigmas, extra)
    dr_mu, dr_sigma, dr_rho = dists.family_dreach_params(
        dist_id, W, mus, sigmas, extra, z)
    dmu_m, dvar_m = contract(*c_mu, dr_mu)
    dmu_s, dvar_s = contract(*c_sigma, dr_sigma)
    if dists.family_has_extra_grads(dist_id):
        dmu_e, dvar_e = contract(*c_rho, dr_rho)
    else:
        dmu_e, dvar_e = zero_fk, zero_fk
    return (mu, var, dmu, dvar, dmu_m, dvar_m, dmu_s, dvar_s, dmu_e, dvar_e)
