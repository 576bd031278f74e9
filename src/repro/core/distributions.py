"""Pluggable channel completion-time distribution families.

The paper's two scenarios — convex optimization on contended VMs and bulk
file transfer over the Internet — have very different completion-time
statistics, but the original stack hard-coded the Gaussian scaling model
``T_i ~ N(w mu_i, (w sigma_i)^2)`` from the core down through the quadrature
kernels. This module makes the per-channel distribution a *family* selected
by a static ``dist_id`` so every layer (survival-integral oracles, the Pallas
kernels and their fused analytic adjoints, the PGD solver, the scheduler, the
simulator and the serving batcher) can run any of:

``normal``
    The paper's model: ``T(w) ~ N(w mu, (w sigma)^2)``.
``lognormal``
    Heavy-tailed service times (WAN transfers, GC pauses): ``T(w) = w R`` with
    ``R`` log-normal *moment-matched* to ``(mu, sigma)`` — the frontier is
    driven by the same two posterior statistics, only the shape changes.
``drift``
    Straggler model: the channel's per-unit rate inflates linearly over the
    course of the work it executes, so the mean is super-linear in the share,
    ``T(w) ~ N(w mu (1 + rho w / 2), (w sigma)^2)`` — a channel drifting at
    ``rho`` per unit work. ``rho = 0`` reduces exactly to ``normal``;
    per-channel ``rho`` lets the scheduler keep a detected straggler enlisted
    (with the drift priced in) instead of quarantining it.
``empirical``
    No parametric assumption: a C-component Gaussian mixture fitted to the
    observed per-unit rates (EM, deterministic init), evaluated exactly.
``defective``
    Failure-aware channels: each attempt fails with per-channel probability
    ``p`` and is re-run, a failed attempt costing ``lam`` of an attempt
    (``lam = 1`` retry pricing: all sunk work lost; ``lam = 0.5`` resume
    pricing: continuous mid-attempt checkpointing loses half an attempt in
    expectation). The completion time, conditioned on eventual success, is
    the geometric compound ``T = A_0 + lam * sum_{i<=N} A_i`` with
    ``N ~ Geom`` failures; the family's law is the Gaussian moment-matched
    to its retry-inflated moments ``a = mu (1 + lam p/q)``,
    ``b^2 = sigma^2 (1 + lam^2 p/q) + lam^2 mu^2 p/q^2`` (``q = 1 - p``) —
    a pure scale family in ``w``, so the whole analytic adjoint structure
    (including ``d/dp``, the failure-probability gradient in ``extra`` row
    0) stays inside the affine feature basis below. ``p = 0`` reduces
    exactly to ``normal``. :func:`family_sample` draws the PHYSICAL retry
    process (failures actually injected): per-channel moments match the
    law exactly, join moments to the Gaussian-shape approximation (same
    status as the Clark fold).

Kernel-facing contract
----------------------

Every family is described to the kernels by ``(dist_id, extra)`` where
``extra`` is a dense ``(E, K)`` float32 array of per-channel shape parameters
(``E = extra_rows(dist_id)``; families without parameters carry one zero row
so launch signatures stay uniform). The math the generalized survival-integral
adjoint needs factors, for every family above, into

    d log C_k / d w_k (t)  =  gate(t) * D_k(t) / C_k(t) * (alpha_k + beta_k t)
    d log C_k / d t   (t)  =  gate(t) * D_k(t) / C_k(t) * (gamma0_k + gamma1_k t) / t

with ``D_k`` a pdf-like per-grid-point numerator and
``alpha/beta/gamma0/gamma1`` per-channel constants (see
``kernels/frontier_grid.py`` for the derivation). That affine-in-``t``
structure is what keeps the fused kernel a two-pass streaming computation: at
most four per-channel accumulators (``P0/P1/Pv0/Pv1``), with the pure scale
families (normal, empirical) and lognormal needing only two — the
per-family accumulator count is part of the autotune working-set model.

Point-mass convention (single-sourced here): a degenerate channel — zero
work, zero spread, or both — is a point mass at its effective mean, and its
CDF is **right-continuous**: ``P(T <= t) = 1`` iff ``t >= mean`` (so a w=0
channel has "already finished" for every ``t >= 0``). Both the strict side
(``t < mean -> 0``) and the non-strict side (``t >= mean -> 1``) follow from
the one expression in :func:`point_mass_cdf`; the quadrature oracles and both
Pallas kernels share it rather than re-deriving the comparison locally.

All functions are pure jnp, broadcasting-agnostic (the vectorized (F, T, K)
reference path and the Pallas kernels' (T, block_f) per-channel slices call
the same code) and differentiable where the math is.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "FAMILIES",
    "EMP_COMPONENTS",
    "phi",
    "Phi",
    "Phi_c",
    "log_Phi",
    "scaled_channel_params",
    "point_mass_cdf",
    "safe_cdf",
    "extra_rows",
    "family_effective_moments",
    "family_cdf",
    "family_pdf_parts",
    "family_adjoint_parts",
    "family_coeffs",
    "family_param_coeffs",
    "family_accumulators",
    "family_features",
    "family_has_extra_grads",
    "family_dreach",
    "family_dreach_params",
    "family_sample",
    "ChannelFamily",
    "Normal",
    "LogNormal",
    "Drift",
    "Empirical",
    "Defective",
    "defective_moments_np",
    "remaining_work_stats",
    "get_family",
    "resolve_family",
    "family_from_extra",
]

FAMILIES = ("normal", "lognormal", "drift", "empirical", "defective")

# Static mixture size for the empirical family: big enough for bimodal
# contention profiles, small enough that the kernel's per-channel inner loop
# stays register-resident.
EMP_COMPONENTS = 3

_SQRT2 = 1.4142135623730951
_SQRT_2PI = 2.5066282746310002
_TINY = 1e-20  # safe-log floor; anything below the t-grid's resolution

# Survival-probability floor for the defective family: p is clamped to
# 1 - _Q_FLOOR so the p -> 1 limit (expected retries diverge) stays finite
# in every kernel; at the clamp the channel is priced as ~1e6 expected
# retries, which any solver already treats as "never assign work here".
_Q_FLOOR = 1e-6


# --------------------------------------------------------------------------
# standard-normal primitives (moved verbatim from core/normal.py; that module
# re-exports these for compatibility)
# --------------------------------------------------------------------------

def phi(x: jax.Array) -> jax.Array:
    """Standard normal pdf."""
    return jnp.exp(-0.5 * x * x) / _SQRT_2PI


# erfc(x) = t exp(-x^2 + P(t)) with t = 1/(1 + x/2) for x >= 0: the Chebyshev
# fit of Press et al., Numerical Recipes (2nd ed., section 6.2, ``erfcc``),
# fractional error < 1.2e-7 for every x >= 0. The error is RELATIVE, so the
# lower tail of Phi keeps its digits down to the kernels' CDF floor instead of
# cancelling to 0 in 0.5 * (1 + erf). Only mul/add/div/exp: Mosaic (the TPU
# kernel compiler) has no erf, and this single definition is what the Pallas
# kernels, the pure-jnp oracles and the XLA path all evaluate.
_ERFC_COEFFS = (-1.26551223, 1.00002368, 0.37409196, 0.09678418, -0.18628806,
                0.27886807, -1.13520398, 1.48851587, -0.82215223, 0.17087277)


def _erfc_nonneg(x):
    """erfc(x) for x >= 0 (see ``_ERFC_COEFFS``)."""
    t = 1.0 / (1.0 + 0.5 * x)
    poly = _ERFC_COEFFS[-1]
    for c in reversed(_ERFC_COEFFS[:-1]):
        poly = c + t * poly
    return t * jnp.exp(poly - x * x)


@jax.custom_jvp
def Phi(x: jax.Array) -> jax.Array:
    """Standard normal cdf, accurate to < 1e-6 absolute and 1.2e-7 relative
    in the lower tail; its derivative is exactly :func:`phi`."""
    tail = 0.5 * _erfc_nonneg(jnp.abs(x) / _SQRT2)   # P(Z > |x|)
    return jnp.where(x < 0.0, tail, 1.0 - tail)


@Phi.defjvp
def _Phi_jvp(primals, tangents):
    (x,), (dx,) = primals, tangents
    return Phi(x), phi(x) * dx


def Phi_c(x: jax.Array) -> jax.Array:
    """Standard normal survival function 1 - Phi(x), numerically stable tail."""
    return Phi(-x)


def log_Phi(x: jax.Array) -> jax.Array:
    """log CDF, stable for moderately negative x (sufficient for our grids)."""
    return jnp.log(jnp.clip(Phi(x), 1e-300, 1.0))


def scaled_channel_params(w, mu, sigma):
    """Per-channel Normal completion-time params for work fraction ``w``.

    T_i ~ N(w*mu_i, (w*sigma_i)^2)  (the paper's scaling assumption; other
    families go through :func:`family_effective_moments`).
    """
    w = jnp.asarray(w)
    return w * mu, w * sigma


def point_mass_cdf(t, mean):
    """CDF of a point mass at ``mean``: right-continuous, 1 iff ``t >= mean``.

    THE degenerate-channel convention. Every call site (safe_cdf, the
    reference quadratures, both Pallas kernel bodies) uses this expression so
    the strict side (t < mean -> 0) and the non-strict side (t >= mean -> 1)
    can never drift apart between layers.
    """
    t = jnp.asarray(t)
    return (t >= mean).astype(t.dtype if jnp.issubdtype(t.dtype, jnp.floating)
                              else jnp.float32)


def safe_cdf(t, mean, std):
    """CDF of N(mean, std^2) at t, treating std==0 as a point mass at ``mean``.

    For w=0 channels mean is also 0, so the channel contributes CDF 1 for
    t>=0 ("no work -> already finished"). The degenerate branch follows
    :func:`point_mass_cdf` (right-continuous at t == mean).
    """
    std_ok = std > 0.0
    z = (t - mean) / jnp.where(std_ok, std, 1.0)
    return jnp.where(std_ok, Phi(z), point_mass_cdf(t, mean))


# --------------------------------------------------------------------------
# family math, selected by static dist_id
# --------------------------------------------------------------------------

def _check_dist(dist_id: str) -> None:
    if dist_id not in FAMILIES:
        raise ValueError(f"dist_id must be one of {FAMILIES}, got {dist_id!r}")


def extra_rows(dist_id: str) -> int:
    """Rows of the (E, K) ``extra`` parameter array each family carries.

    Families without shape parameters still carry one zero row so the kernel
    launch signature (and its BlockSpec) is uniform across families.
    """
    _check_dist(dist_id)
    if dist_id == "empirical":
        return 3 * EMP_COMPONENTS
    if dist_id == "defective":
        return 2  # row 0: failure prob p (differentiable); row 1: pricing lam
    return 1


def _mixture_stats(extra):
    """(m_mix, s_mix) of the per-unit-rate Gaussian mixture in ``extra``.

    extra rows: [pi_0..pi_{C-1}, m_0..m_{C-1}, s_0..s_{C-1}].
    """
    C = EMP_COMPONENTS
    pis = [extra[c] for c in range(C)]
    ms = [extra[C + c] for c in range(C)]
    ss = [extra[2 * C + c] for c in range(C)]
    m_mix = sum(p * m for p, m in zip(pis, ms))
    e2 = sum(p * (s * s + m * m) for p, m, s in zip(pis, ms, ss))
    s_mix = jnp.sqrt(jnp.maximum(e2 - m_mix * m_mix, 0.0))
    return m_mix, s_mix


def lognormal_shape_np(mu, sigma):
    """Numpy twin of :func:`_lognormal_shape` for host-side samplers.

    Returns ``(s_l, base)`` with ``R ~ LN(base, s_l^2)`` moment-matched to
    ``(mu, sigma)``. The simulator and :func:`family_sample` both draw
    through this, so ground truth and the solver's quadrature can only share
    one definition of the moment matching.
    """
    mu = np.maximum(np.asarray(mu, np.float64), 1e-300)
    s2 = np.log1p((np.asarray(sigma, np.float64) / mu) ** 2)
    return np.sqrt(s2), np.log(mu) - 0.5 * s2


def _lognormal_shape(mu, sigma):
    """(s_l, base) of the moment-matched log-normal per-unit rate.

    R ~ LN(log(mu) - s_l^2/2, s_l^2) has mean mu and std sigma when
    s_l^2 = log(1 + (sigma/mu)^2); the CoV is scale-free, so s_l does not
    depend on the work share w. ``base = log(mu) - s_l^2/2`` (add log(w) for
    the scaled completion time).
    """
    mu_ok = mu > 0.0
    safe_mu = jnp.where(mu_ok, mu, 1.0)
    s2 = jnp.log1p(jnp.square(sigma / safe_mu))
    s_l = jnp.sqrt(s2)
    base = jnp.log(safe_mu) - 0.5 * s2
    return s_l, base


def _drift_mean_scale(w, extra):
    """g(w) = w (1 + rho w / 2): the drift family's mean multiplier."""
    rho = extra[0]
    return w * (1.0 + 0.5 * rho * w)


def defective_moments_np(mu, sigma, p, lam):
    """Numpy twin of :func:`_defective_ab` for host-side samplers.

    Returns the retry-inflated per-unit moments ``(a, b)`` of the defective
    family: with ``q = 1 - p`` (floored at ``1e-6``) and failed attempts
    costing ``lam`` of an attempt,

        a   = mu * (1 + lam p/q)
        b^2 = sigma^2 (1 + lam^2 p/q) + lam^2 mu^2 p/q^2

    exactly the mean/variance of ``T = A_0 + lam sum_{i<=N} A_i`` with
    ``A_i ~ N(mu, sigma^2)`` iid and ``N ~ Geom(q)`` failures-before-success
    (``E N = p/q``, ``Var N = p/q^2``). The simulator's retry injection and
    :func:`family_sample` draw that physical process, so the law and its
    ground truth share this one derivation.
    """
    mu = np.asarray(mu, np.float64)
    sigma = np.asarray(sigma, np.float64)
    p = np.clip(np.asarray(p, np.float64), 0.0, 1.0 - _Q_FLOOR)
    lam = np.asarray(lam, np.float64)
    q = 1.0 - p
    ratio = p / q
    a = mu * (1.0 + lam * ratio)
    b2 = sigma * sigma * (1.0 + lam * lam * ratio) \
        + (lam * mu) ** 2 * ratio / q
    return a, np.sqrt(np.maximum(b2, 0.0))


def _defective_ab(mu, sigma, extra):
    """Retry-inflated per-unit moments (a, b) of the defective family.

    ``extra[0] = p`` (per-attempt failure probability, clamped to
    ``[0, 1 - _Q_FLOOR]``), ``extra[1] = lam`` (pricing: fraction of an
    attempt a failure costs). See :func:`defective_moments_np` for the
    derivation; ``T(w) ~ N(w a, (w b)^2)`` — a pure scale family, so every
    kernel treats it exactly like ``normal`` with ``(a, b)`` substituted.
    ``p = 0`` gives ``(a, b) = (mu, sigma)`` identically.

    Only the UPPER side is clamped: clamping at 0 would put the valid
    boundary value ``p = 0`` on a max-tie, where autodiff splits the
    cotangent 0.5/0.5 and the analytic adjoint would disagree with it by
    exactly 2x. Negative ``p`` is rejected at the API boundary
    (:class:`Defective`) and by the sanitizer instead.
    """
    p = jnp.minimum(extra[0], 1.0 - _Q_FLOOR)
    lam = extra[1]
    q = 1.0 - p
    ratio = p / q
    a = mu * (1.0 + lam * ratio)
    b2 = sigma * sigma * (1.0 + lam * lam * ratio) \
        + jnp.square(lam * mu) * ratio / q
    return a, jnp.sqrt(jnp.maximum(b2, 0.0))


def family_effective_moments(dist_id: str, w, mu, sigma, extra):
    """(mean, std) of the completion time T(w) under the family.

    This is what the integration reach ``tmax = max_k(mean_k + z std_k)``
    and the scheduler's moment predictions consume. Lognormal is
    moment-matched by construction, so its effective moments equal the
    normal family's.
    """
    _check_dist(dist_id)
    if dist_id in ("normal", "lognormal"):
        return w * mu, w * sigma
    if dist_id == "drift":
        return mu * _drift_mean_scale(w, extra), w * sigma
    if dist_id == "defective":
        a, b = _defective_ab(mu, sigma, extra)
        return w * a, w * b
    m_mix, s_mix = _mixture_stats(extra)
    return w * m_mix, w * s_mix


def _raw_cdf(dist_id: str, t, w, mu, sigma, extra, ok, safe_w):
    """Family CDF with degenerate denominators substituted (gate with ``ok``)."""
    if dist_id == "normal":
        std = w * sigma
        z = (t - w * mu) / jnp.where(ok, std, 1.0)
        return Phi(z)
    if dist_id == "lognormal":
        s_l, base = _lognormal_shape(mu, sigma)
        s_safe = jnp.where(ok, s_l, 1.0)
        z = (jnp.log(jnp.maximum(t, _TINY)) - jnp.log(safe_w) - base) / s_safe
        return Phi(z)
    if dist_id == "drift":
        m_d = mu * _drift_mean_scale(w, extra)
        std = w * sigma
        z = (t - m_d) / jnp.where(ok, std, 1.0)
        return Phi(z)
    if dist_id == "defective":
        a, b = _defective_ab(mu, sigma, extra)
        z = (t - w * a) / jnp.where(ok, w * b, 1.0)
        return Phi(z)
    # empirical mixture: sum_c pi_c Phi((t - w m_c)/(w s_c)); a zero-spread
    # component degenerates to its own (right-continuous) point mass
    C = EMP_COMPONENTS
    acc = 0.0
    for c in range(C):
        pi_c, m_c, s_c = extra[c], extra[C + c], extra[2 * C + c]
        c_ok = ok & (s_c > 0.0)
        z_c = (t - w * m_c) / jnp.where(c_ok, w * s_c, 1.0)
        cdf_c = jnp.where(c_ok, Phi(z_c), point_mass_cdf(t, w * m_c))
        acc = acc + pi_c * cdf_c
    return acc


def _family_ok(dist_id: str, w, mu, sigma, extra):
    """Non-degenerate mask: channels with an absolutely continuous T(w)."""
    if dist_id == "lognormal":
        return (w > 0.0) & (sigma > 0.0) & (mu > 0.0)
    if dist_id == "empirical":
        _, s_mix = _mixture_stats(extra)
        return (w > 0.0) & (s_mix > 0.0)
    if dist_id == "defective":
        # b can be positive even when sigma == 0 (retry variance from mu)
        _, b = _defective_ab(mu, sigma, extra)
        return (w * b) > 0.0
    return (w * sigma) > 0.0


def family_cdf(dist_id: str, t, w, mu, sigma, extra):
    """P(T(w) <= t) for one channel (broadcasting over any leading shape).

    Degenerate channels (w=0, sigma=0, or a spread-free mixture) are a point
    mass at the family's effective mean, right-continuous per
    :func:`point_mass_cdf`.
    """
    _check_dist(dist_id)
    ok = _family_ok(dist_id, w, mu, sigma, extra)
    safe_w = jnp.where(w > 0.0, w, 1.0)
    raw = _raw_cdf(dist_id, t, w, mu, sigma, extra, ok, safe_w)
    m_eff, _ = family_effective_moments(dist_id, w, mu, sigma, extra)
    return jnp.where(ok, raw, point_mass_cdf(t, m_eff))


def family_adjoint_parts(dist_id: str, t, w, mu, sigma, extra):
    """Per-grid-point adjoint pieces: ``(cdf_raw, D, ok, z)``.

    ``cdf_raw`` is the un-substituted CDF (drives the clip/saturation gates),
    ``D`` the pdf-like numerator with ``dC/dw = D * (alpha + beta t)`` and
    ``dC/dt = D * (gamma0 + gamma1 t) / t`` for the per-channel constants
    from :func:`family_coeffs`, and ``ok`` the non-degenerate mask (False
    rows contribute no direct gradient — a point mass is flat a.e.).
    ``z`` is the family's standardized score at each grid point — the third
    basis feature the *parameter* adjoints of the lognormal family contract
    against (``dz/dmu`` and ``dz/dsigma`` are affine in z, not in t, because
    the shape parameter ``s_l`` itself moves with (mu, sigma)); families that
    never use the z feature return zeros (empirical has no single z).
    """
    _check_dist(dist_id)
    ok = _family_ok(dist_id, w, mu, sigma, extra)
    safe_w = jnp.where(w > 0.0, w, 1.0)
    cdf_raw = _raw_cdf(dist_id, t, w, mu, sigma, extra, ok, safe_w)
    if dist_id == "normal":
        z = (t - w * mu) / jnp.where(ok, w * sigma, 1.0)
        D = phi(z)
    elif dist_id == "lognormal":
        s_l, base = _lognormal_shape(mu, sigma)
        z = (jnp.log(jnp.maximum(t, _TINY)) - jnp.log(safe_w)
             - base) / jnp.where(ok, s_l, 1.0)
        D = phi(z)
    elif dist_id == "drift":
        m_d = mu * _drift_mean_scale(w, extra)
        z = (t - m_d) / jnp.where(ok, w * sigma, 1.0)
        D = phi(z)
    elif dist_id == "defective":
        a, b = _defective_ab(mu, sigma, extra)
        z = (t - w * a) / jnp.where(ok, w * b, 1.0)
        D = phi(z)
    else:  # empirical: D = sum_c pi_c phi(z_c) / s_c; no single z score
        C = EMP_COMPONENTS
        D = 0.0
        for c in range(C):
            pi_c, m_c, s_c = extra[c], extra[C + c], extra[2 * C + c]
            c_ok = ok & (s_c > 0.0)
            z_c = (t - w * m_c) / jnp.where(c_ok, w * s_c, 1.0)
            D = D + jnp.where(c_ok, pi_c / jnp.where(c_ok, s_c, 1.0), 0.0) \
                * phi(z_c)
        z = jnp.zeros_like(D)
    return cdf_raw, D, ok, z


def family_pdf_parts(dist_id: str, t, w, mu, sigma, extra):
    """Back-compat wrapper over :func:`family_adjoint_parts` without ``z``."""
    cdf_raw, D, ok, _ = family_adjoint_parts(dist_id, t, w, mu, sigma, extra)
    return cdf_raw, D, ok


def family_coeffs(dist_id: str, w, mu, sigma, extra):
    """Per-channel adjoint constants ``(alpha, beta, gamma0, gamma1)``.

    With ``D`` from :func:`family_pdf_parts`:

        dC/dw |_t  = D(t) * (alpha + beta * t)          (fixed-grid term)
        dC/dt |_t  = D(t) * (gamma0 + gamma1 * t) / t   (moving-grid term)

    The companion :func:`family_dreach` supplies ``d(mean + z*std)/dw`` for
    the tmax cotangent on the argmax channel. Degenerate channels get
    all-zero constants (their
    point-mass CDF is flat a.e.; they still receive the grid-path gradient
    through ``dreach`` when they set the integration end). Note gamma* are
    defined so the kernels' accumulators contract them exactly:
    ``sum_j a_jk t_j * (dC/dt)/D = gamma0 * P0 + gamma1 * P1``.
    """
    _check_dist(dist_id)
    ok = _family_ok(dist_id, w, mu, sigma, extra)
    zero = jnp.zeros_like(w * mu)

    def guard(x):
        return jnp.where(ok, x, 0.0)

    if dist_id == "normal":
        inv_w2s = 1.0 / jnp.where(ok, w * w * sigma, 1.0)
        inv_s = 1.0 / jnp.where(ok, w * sigma, 1.0)
        return zero, guard(-inv_w2s), zero, guard(inv_s)
    if dist_id == "lognormal":
        s_l, _ = _lognormal_shape(mu, sigma)
        inv_ws = 1.0 / jnp.where(ok, w * s_l, 1.0)
        # dz/dw = -1/(w s_l) (t-free); dz/dt = 1/(t s_l): gamma0 contracts P0
        inv_sl = 1.0 / jnp.where(ok, s_l, 1.0)
        return guard(-inv_ws), zero, guard(inv_sl), zero
    if dist_id == "drift":
        rho = extra[0]
        inv_w2s = 1.0 / jnp.where(ok, w * w * sigma, 1.0)
        inv_s = 1.0 / jnp.where(ok, w * sigma, 1.0)
        # z = (t - mu g(w)) / (w sigma), g = w(1 + rho w/2):
        # dz/dw = -mu g'/(w s) - z/w collapses to -(rho mu)/(2 sigma) - t/(w^2 s)
        alpha = guard(-0.5 * rho * mu / jnp.where(ok, sigma, 1.0))
        return alpha, guard(-inv_w2s), zero, guard(inv_s)
    if dist_id == "defective":
        # pure scale family: identical to normal with (a, b) substituted
        _, b = _defective_ab(mu, sigma, extra)
        inv_w2b = 1.0 / jnp.where(ok, w * w * b, 1.0)
        inv_b = 1.0 / jnp.where(ok, w * b, 1.0)
        return zero, guard(-inv_w2b), zero, guard(inv_b)
    # empirical: scale family in w -> dC/dw = -(t/w) pdf, dC/dt = pdf = D/w
    inv_w2 = 1.0 / jnp.where(ok, w * w, 1.0)
    inv_w = 1.0 / jnp.where(ok, w, 1.0)
    return zero, guard(-inv_w2), zero, guard(inv_w)


def family_accumulators(dist_id: str) -> Tuple[bool, bool]:
    """Which per-channel accumulator pairs the W-only fused adjoint needs.

    Returns ``(use_p0, use_p1)``: P0/Pv0 contract the t-free (alpha, gamma0)
    coefficients, P1/Pv1 the t-weighted (beta, gamma1) ones. Pure scale
    families (normal, empirical) and drift keep P1; lognormal's log-space
    z-score is t-free in dw and needs P0 instead; drift's affine dz/dw needs
    both — 4 live (K, block_f) accumulators instead of 2, which is why the
    family is part of the autotune working-set model and cache key. The
    full-parameter adjoint needs the wider :func:`family_features` basis.
    """
    use_1, use_t, _ = family_features(dist_id, params=False)
    return use_1, use_t


def family_features(dist_id: str, params: bool = False
                    ) -> Tuple[bool, bool, bool]:
    """Accumulator basis the fused adjoint contracts against.

    Returns ``(use_1, use_t, use_z)``: every live feature f costs a
    ``(K, block_f)`` accumulator pair (``Pf`` for the mu cotangent, ``Pvf``
    for the fused var cotangent). With ``params=False`` (W-gradients only —
    the PGD path) this is the legacy :func:`family_accumulators` set; with
    ``params=True`` the mus/sigmas/extra adjoints widen the basis:

    * ``normal``/``drift``: dz/dmu is t-free and dz/dsigma = -z/sigma expands
      to an affine-in-t form, so the {1, t} basis covers every parameter.
    * ``lognormal``: the moment-matched shape ``s_l(mu, sigma)`` makes
      dz/dmu and dz/dsigma affine in **z** itself (not t) — the z feature
      joins the basis, and that family alone contracts Pz/Pvz.
    * ``empirical``: the channel's (mu, sigma) never enter the mixture CDF —
      no parameter adjoints, the {t} basis stays.
    * ``defective``: the W-adjoint is the normal family's with (a, b)
      substituted ({t} basis); the parameter adjoints move the composite
      spread ``b(mu, sigma, p)``, so dz/dmu and dz/dp pick up -z (db/d.)/b
      terms — the z feature joins and all three features go live, the
      widest working set of any family (part of the autotune model).
    """
    _check_dist(dist_id)
    if not params:
        return {
            "normal": (False, True, False),
            "lognormal": (True, False, False),
            "drift": (True, True, False),
            "empirical": (False, True, False),
            "defective": (False, True, False),
        }[dist_id]
    return {
        "normal": (True, True, False),
        "lognormal": (True, False, True),
        "drift": (True, True, False),
        "empirical": (False, True, False),
        "defective": (True, True, True),
    }[dist_id]


def family_has_extra_grads(dist_id: str) -> bool:
    """Whether the family's ``extra`` row 0 carries a differentiable shape
    parameter (drift's per-channel ``rho``, defective's failure probability
    ``p``). The empirical mixture's fitted parameters are solve constants by
    contract (re-fit, not descended), and the defective family's pricing
    constant ``lam`` (extra row 1) is a mode switch, not a statistic — its
    cotangent is documented-zero."""
    _check_dist(dist_id)
    return dist_id in ("drift", "defective")


def family_param_coeffs(dist_id: str, w, mu, sigma, extra):
    """Per-channel adjoint constants for the *channel-statistic* parameters.

    Returns ``(c_mu, c_sigma, c_rho)``, each a triple ``(a, b, c)`` of
    per-channel coefficient arrays against the (1, t, z) feature basis of
    :func:`family_features`:

        d log C_k / d theta_k |_t = g_jk * (a_k + b_k t + c_k z_jk)

    with ``g_jk`` the same gated inverse-Mills ratio the W-adjoint uses, and
    ``z_jk`` the standardized score from :func:`family_adjoint_parts`.
    ``c_rho`` is the coefficient triple for ``extra`` row 0 and is all-zero
    unless :func:`family_has_extra_grads` (drift). Degenerate (point-mass)
    channels get all-zero constants, exactly like :func:`family_coeffs` —
    they still receive the moving-grid term through
    :func:`family_dreach_params` when they set the integration end.

    Derivations (z-scores as in :func:`family_adjoint_parts`):

    * normal, z = (t - w mu)/(w sigma):
        dz/dmu    = -1/sigma                              -> (a, 0, 0)
        dz/dsigma = -z/sigma = mu/sigma^2 - t/(w sigma^2) -> (a, b, 0)
    * lognormal, z = (log t - log w - base)/s_l with v = (sigma/mu)^2,
      s_l^2 = log(1+v), base = log mu - s_l^2/2:
        ds_l/dmu    = -v/(mu (1+v) s_l),  dbase/dmu    = 1/mu + v/(mu (1+v))
        ds_l/dsigma =  v/(sigma (1+v) s_l), dbase/dsigma = -v/(sigma (1+v))
        dz/dtheta = -(dbase/dtheta)/s_l - z (ds_l/dtheta)/s_l -> (a, 0, c)
    * drift, z = (t - mu g(w))/(w sigma), g = w(1 + rho w/2):
        dz/dmu    = -g/(w sigma)                          -> (a, 0, 0)
        dz/dsigma = -z/sigma = mu g/(w sigma^2) - t/(w sigma^2) -> (a, b, 0)
        dz/drho   = -mu w/(2 sigma)                       -> (a, 0, 0)
    * defective, z = (t - w a)/(w b) with q = 1-p, r = p/q,
      a = mu (1 + lam r), b^2 = sigma^2 (1 + lam^2 r) + lam^2 mu^2 r/q:
      every parameter theta gives dz/dtheta = -(da/dtheta)/b
      - z (db/dtheta)/b, so each is an (a, 0, c) pair against {1, z}:
        da/dmu = 1 + lam r,   db/dmu    = lam^2 mu (r/q) / b
        da/dsigma = 0,        db/dsigma = sigma (1 + lam^2 r) / b
        da/dp = mu lam / q^2,
        d(b^2)/dp = lam^2 (sigma^2/q^2 + mu^2 (1+p)/q^3),
        db/dp = d(b^2)/dp / (2 b)
      ``c_rho`` is the coefficient for p (extra row 0); lam (row 1) is a
      pricing constant with documented-zero cotangent.
    * empirical: all zero (mus/sigmas unused; mixture params are constants).
    """
    _check_dist(dist_id)
    ok = _family_ok(dist_id, w, mu, sigma, extra)
    zero = jnp.zeros_like(w * mu)

    def guard(x):
        return jnp.where(ok, x, 0.0)

    z3 = (zero, zero, zero)
    if dist_id == "normal":
        inv_s = 1.0 / jnp.where(ok, sigma, 1.0)
        inv_ws2 = 1.0 / jnp.where(ok, w * sigma * sigma, 1.0)
        c_mu = (guard(-inv_s), zero, zero)
        c_sigma = (guard(mu * inv_s * inv_s), guard(-inv_ws2), zero)
        return c_mu, c_sigma, z3
    if dist_id == "lognormal":
        mu_ok = mu > 0.0
        safe_mu = jnp.where(mu_ok, mu, 1.0)
        safe_sg = jnp.where(sigma > 0.0, sigma, 1.0)
        v = jnp.square(sigma / safe_mu)
        s_l, _ = _lognormal_shape(mu, sigma)
        s_safe = jnp.where(ok, s_l, 1.0)
        r = v / (1.0 + v)                      # = d s_l^2 scale factor
        dbase_dmu = (1.0 + r) / safe_mu
        dsl_dmu = -r / (safe_mu * s_safe)
        dbase_dsg = -r / safe_sg
        dsl_dsg = r / (safe_sg * s_safe)
        c_mu = (guard(-dbase_dmu / s_safe), zero,
                guard(-dsl_dmu / s_safe))
        c_sigma = (guard(-dbase_dsg / s_safe), zero,
                   guard(-dsl_dsg / s_safe))
        return c_mu, c_sigma, z3
    if dist_id == "drift":
        g = _drift_mean_scale(w, extra)
        inv_ws = 1.0 / jnp.where(ok, w * sigma, 1.0)
        inv_ws2 = 1.0 / jnp.where(ok, w * sigma * sigma, 1.0)
        c_mu = (guard(-g * inv_ws), zero, zero)
        c_sigma = (guard(mu * g * inv_ws2), guard(-inv_ws2), zero)
        c_rho = (guard(-0.5 * mu * w / jnp.where(ok, sigma, 1.0)), zero, zero)
        return c_mu, c_sigma, c_rho
    if dist_id == "defective":
        p = jnp.minimum(extra[0], 1.0 - _Q_FLOOR)
        lam = extra[1]
        q = 1.0 - p
        ratio = p / q
        _, b = _defective_ab(mu, sigma, extra)
        inv_b = 1.0 / jnp.where(ok, b, 1.0)
        inv_b2 = inv_b * inv_b
        da_dmu = 1.0 + lam * ratio
        db_dmu_b = lam * lam * mu * (ratio / q) * inv_b2   # (db/dmu)/b
        db_dsg_b = sigma * (1.0 + lam * lam * ratio) * inv_b2
        da_dp = mu * lam / (q * q)
        db2_dp = lam * lam * (sigma * sigma / (q * q)
                              + mu * mu * (1.0 + p) / (q * q * q))
        db_dp_b = 0.5 * db2_dp * inv_b2                    # (db/dp)/b
        c_mu = (guard(-da_dmu * inv_b), zero, guard(-db_dmu_b))
        c_sigma = (zero, zero, guard(-db_dsg_b))
        c_p = (guard(-da_dp * inv_b), zero, guard(-db_dp_b))
        return c_mu, c_sigma, c_p
    # empirical: the mixture CDF never reads (mu, sigma); extra is a constant
    return z3, z3, z3


def family_dreach(dist_id: str, w, mu, sigma, extra, z: float):
    """d(reach)/dw per channel, reach = effective mean + z * effective std."""
    _check_dist(dist_id)
    if dist_id in ("normal", "lognormal"):
        return mu + z * sigma
    if dist_id == "drift":
        rho = extra[0]
        return mu * (1.0 + rho * w) + z * sigma
    if dist_id == "defective":
        a, b = _defective_ab(mu, sigma, extra)
        return a + z * b
    m_mix, s_mix = _mixture_stats(extra)
    return (m_mix + z * s_mix) * jnp.ones_like(w)


def family_dreach_params(dist_id: str, w, mu, sigma, extra, z: float):
    """``(d reach/dmu, d reach/dsigma, d reach/drho)`` per channel.

    The parameter twin of :func:`family_dreach`: when a channel's statistic
    moves, the integration end ``tmax = max_k reach_k`` moves with it on the
    argmax channel, so every parameter adjoint carries the same moving-grid
    term the W-adjoint does. ``reach = mean_eff + z * std_eff``:

    * normal / lognormal: mean = w mu, std = w sigma -> (w, z w, 0)
    * drift: mean = mu g(w) with g = w(1 + rho w/2), std = w sigma
      -> (g(w), z w, mu w^2/2)
    * defective: mean = w a, std = w b -> w (da/d. + z db/d.) with the
      chain-rule pieces from :func:`family_param_coeffs`; db-terms are
      gated on b > 0 (a spread-free channel's reach moves only through a).
    * empirical: the mixture stats ignore (mu, sigma) -> all zero.
    """
    _check_dist(dist_id)
    ones = jnp.ones_like(w * mu)
    zero = jnp.zeros_like(ones)
    if dist_id in ("normal", "lognormal"):
        return w * ones, z * w * ones, zero
    if dist_id == "drift":
        g = _drift_mean_scale(w, extra)
        return g * ones, z * w * ones, 0.5 * mu * w * w * ones
    if dist_id == "defective":
        p = jnp.minimum(extra[0], 1.0 - _Q_FLOOR)
        lam = extra[1]
        q = 1.0 - p
        ratio = p / q
        _, b = _defective_ab(mu, sigma, extra)
        b_ok = b > 0.0
        inv_b = 1.0 / jnp.where(b_ok, b, 1.0)
        db_dmu = jnp.where(b_ok, lam * lam * mu * (ratio / q) * inv_b, 0.0)
        db_dsg = jnp.where(b_ok, sigma * (1.0 + lam * lam * ratio) * inv_b,
                           0.0)
        db2_dp = lam * lam * (sigma * sigma / (q * q)
                              + mu * mu * (1.0 + p) / (q * q * q))
        db_dp = jnp.where(b_ok, 0.5 * db2_dp * inv_b, 0.0)
        d_mu = w * ((1.0 + lam * ratio) + z * db_dmu)
        d_sg = w * z * db_dsg
        d_p = w * (mu * lam / (q * q) + z * db_dp)
        return d_mu * ones, d_sg * ones, d_p * ones
    return zero, zero, zero


def family_sample(dist_id: str, rng: np.random.Generator, w, mu, sigma, extra,
                  size: int) -> np.ndarray:
    """Draw ``size`` completion-time samples T(w) per channel (numpy, host).

    Shapes: w/mu/sigma (K,), extra (E, K) -> (size, K). The Monte-Carlo
    ground truth for the family: the oracle tests sample through this, and
    ``sim.ClusterSim`` mirrors the same formulas (via
    :func:`lognormal_shape_np` and the drift mean term) with stream-shaped
    per-fleet draws.
    """
    _check_dist(dist_id)
    w = np.asarray(w, np.float64)
    mu = np.asarray(mu, np.float64)
    sigma = np.asarray(sigma, np.float64)
    extra = np.asarray(extra, np.float64)
    if dist_id == "normal":
        return w * rng.normal(mu, sigma, size=(size, w.shape[0]))
    if dist_id == "lognormal":
        s_l, base = lognormal_shape_np(mu, sigma)
        r = rng.lognormal(base, s_l, size=(size, w.shape[0]))
        return w * r
    if dist_id == "drift":
        rho = extra[0]
        base = w * rng.normal(mu, sigma, size=(size, w.shape[0]))
        return base + 0.5 * rho * mu * w * w  # deterministic mean inflation
    if dist_id == "defective":
        # the PHYSICAL retry process, failures actually injected:
        # T = w (A_0 + lam sum_{i<=N} A_i), A_i ~ N(mu, sigma^2) iid,
        # N ~ Geom failures-before-success. Per-channel moments match the
        # family's (a, b) exactly; the JOIN inherits the Gaussian shape
        # approximation (the model law is the moment-matched normal).
        p = np.clip(extra[0], 0.0, 1.0 - _Q_FLOOR)
        lam = extra[1]
        K = w.shape[0]
        succ = rng.normal(mu, sigma, size=(size, K))
        nfail = rng.geometric(1.0 - p, size=(size, K)) - 1
        # sum of N iid normals drawn exactly: N(N mu, N sigma^2)
        lost = nfail * mu + np.sqrt(nfail.astype(np.float64)) * sigma \
            * rng.standard_normal((size, K))
        return w * (succ + lam * lost)
    C = EMP_COMPONENTS
    pis = extra[:C].T                       # (K, C)
    ms, ss = extra[C:2 * C].T, extra[2 * C:3 * C].T
    K = w.shape[0]
    out = np.empty((size, K))
    for k in range(K):
        comp = rng.choice(C, size=size, p=pis[k] / pis[k].sum())
        out[:, k] = w[k] * rng.normal(ms[k][comp], ss[k][comp])
    return out


# --------------------------------------------------------------------------
# the ChannelFamily objects (host-side API surface)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ChannelFamily:
    """A completion-time distribution family: static ``dist_id`` + params.

    Instances are what the user-facing layers accept (``family=`` on
    ``frontier_moments``, ``frontier_kch``, ``optimize_weights``,
    ``UncertaintyAwareBalancer``, ``PartitionedBatcher``); plain family-name
    strings are accepted everywhere too and resolved via :func:`get_family`.
    :func:`resolve_family` lowers either form to the kernel-facing
    ``(dist_id, extra)`` pair.
    """

    dist_id: str = "normal"

    def extra(self, k: int) -> np.ndarray:
        """(E, K) float32 per-channel shape parameters for the kernels."""
        return np.zeros((extra_rows(self.dist_id), k), np.float32)

    def state_dict(self) -> dict:
        return {"dist_id": self.dist_id}


class Normal(ChannelFamily):
    def __init__(self):
        super().__init__(dist_id="normal")


class LogNormal(ChannelFamily):
    def __init__(self):
        super().__init__(dist_id="lognormal")


@dataclass(frozen=True)
class Drift(ChannelFamily):
    """Straggler family: per-channel drift rate ``rho`` (scalar broadcasts).

    ``rho[k] = 0`` reduces channel k to the normal family exactly, so one
    Drift family covers a mixed fleet — which is how the straggler policy
    prices detected stragglers instead of dropping them.
    """

    rho: object = 0.0

    def __init__(self, rho=0.0):
        super().__init__(dist_id="drift")
        object.__setattr__(self, "rho", np.asarray(rho, np.float32))

    def extra(self, k: int) -> np.ndarray:
        rho = np.broadcast_to(np.asarray(self.rho, np.float32), (k,))
        return rho[None, :].copy()

    def state_dict(self) -> dict:
        return {"dist_id": "drift", "rho": np.asarray(self.rho).tolist()}


# Failure pricing modes: the fraction of an attempt a failed attempt costs.
# "retry" re-runs from scratch (all sunk work lost); "resume" assumes
# continuous mid-attempt checkpointing, losing half an attempt in expectation
# (failure point uniform over the attempt).
DEFECTIVE_PRICING = {"retry": 1.0, "resume": 0.5}


@dataclass(frozen=True)
class Defective(ChannelFamily):
    """Failure-aware family: per-channel attempt-failure probability ``p``.

    Each attempt on channel k fails independently with probability ``p[k]``
    and is re-run; the pricing mode fixes how much of an attempt a failure
    costs (``"retry"``: 1.0, ``"resume"``: 0.5, or any float in [0, 1]).
    ``p`` may be a scalar (broadcast) or per-channel. ``p = 0`` reduces the
    channel to the normal family exactly, so one Defective family covers a
    fleet where only some channels are flaky — and the solver prices both
    the mean inflation and the retry variance instead of discovering the
    failures as realized stragglers.
    """

    p: object = 0.0
    lam: object = 1.0

    def __init__(self, p=0.0, pricing="retry"):
        super().__init__(dist_id="defective")
        if isinstance(pricing, str):
            if pricing not in DEFECTIVE_PRICING:
                raise ValueError(f"pricing must be one of "
                                 f"{sorted(DEFECTIVE_PRICING)} or a float in "
                                 f"[0, 1], got {pricing!r}")
            lam = DEFECTIVE_PRICING[pricing]
        else:
            lam = float(pricing)
            if not 0.0 <= lam <= 1.0:
                raise ValueError(f"pricing fraction must lie in [0, 1], "
                                 f"got {lam}")
        p_arr = np.asarray(p, np.float32)
        if p_arr.size and (float(p_arr.min()) < 0.0
                           or float(p_arr.max()) > 1.0):
            raise ValueError("failure probabilities must lie in [0, 1], got "
                             f"range [{float(p_arr.min())}, "
                             f"{float(p_arr.max())}]")
        object.__setattr__(self, "p", p_arr)
        object.__setattr__(self, "lam", np.float32(lam))

    def extra(self, k: int) -> np.ndarray:
        p = np.broadcast_to(np.asarray(self.p, np.float32), (k,))
        lam = np.full((k,), self.lam, np.float32)
        return np.stack([p, lam])

    def state_dict(self) -> dict:
        return {"dist_id": "defective", "p": np.asarray(self.p).tolist(),
                "lam": float(self.lam)}


@dataclass(frozen=True)
class Empirical(ChannelFamily):
    """Gaussian-mixture fit of observed per-unit rates (C components/channel).

    ``weights/means/stds`` are (C, K). Build from raw observations with
    :meth:`from_samples` (deterministic quantile-initialized EM, variance
    floored so the kernels never see a spread-free component unless the data
    is literally constant).
    """

    weights: np.ndarray = None
    means: np.ndarray = None
    stds: np.ndarray = None

    def __init__(self, weights, means, stds):
        super().__init__(dist_id="empirical")
        w = np.asarray(weights, np.float32)
        if w.ndim == 1:
            w, means, stds = (np.asarray(a, np.float32)[:, None]
                              for a in (weights, means, stds))
        else:
            means = np.asarray(means, np.float32)
            stds = np.asarray(stds, np.float32)
        if w.shape[0] != EMP_COMPONENTS:
            raise ValueError(f"expected {EMP_COMPONENTS} mixture components, "
                             f"got {w.shape[0]}")
        w = w / np.maximum(w.sum(axis=0, keepdims=True), 1e-12)
        object.__setattr__(self, "weights", w)
        object.__setattr__(self, "means", means)
        object.__setattr__(self, "stds", np.asarray(stds, np.float32))

    @classmethod
    def from_samples(cls, samples, iters: int = 40,
                     var_floor_frac: float = 1e-3) -> "Empirical":
        """Fit per-channel mixtures from observed rates.

        ``samples``: (N, K) array or length-K sequence of 1-D arrays of
        per-unit-work durations. Deterministic: quantile init, fixed EM
        iteration count, no RNG.
        """
        if isinstance(samples, np.ndarray) and samples.ndim == 2:
            cols = [samples[:, k] for k in range(samples.shape[1])]
        else:
            cols = [np.asarray(s, np.float64).ravel() for s in samples]
        C = EMP_COMPONENTS
        W = np.empty((C, len(cols)))
        M = np.empty((C, len(cols)))
        S = np.empty((C, len(cols)))
        for k, x in enumerate(cols):
            W[:, k], M[:, k], S[:, k] = _em_1d(np.asarray(x, np.float64),
                                               C, iters, var_floor_frac)
        return cls(W, M, S)

    def extra(self, k: int) -> np.ndarray:
        if self.weights.shape[1] == 1 and k > 1:
            tile = lambda a: np.broadcast_to(a, (EMP_COMPONENTS, k))
            return np.concatenate([tile(self.weights), tile(self.means),
                                   tile(self.stds)], axis=0).astype(np.float32)
        if self.weights.shape[1] != k:
            raise ValueError(f"family fitted for K={self.weights.shape[1]} "
                             f"channels, asked for K={k}")
        return np.concatenate([self.weights, self.means, self.stds],
                              axis=0).astype(np.float32)

    def state_dict(self) -> dict:
        return {"dist_id": "empirical", "weights": self.weights.tolist(),
                "means": self.means.tolist(), "stds": self.stds.tolist()}


def _em_1d(x: np.ndarray, C: int, iters: int, var_floor_frac: float):
    """Deterministic 1-D Gaussian-mixture EM (quantile init, floored vars)."""
    n = x.shape[0]
    if n == 0:
        raise ValueError("cannot fit an empirical family from zero samples")
    spread = max(float(x.std()), abs(float(x.mean())) * 1e-6, 1e-12)
    floor = (var_floor_frac * spread) ** 2
    mus = np.quantile(x, (np.arange(C) + 0.5) / C)
    vars_ = np.full(C, max(spread ** 2 / C, floor))
    pis = np.full(C, 1.0 / C)
    for _ in range(iters):
        # E-step in log space for stability
        logp = (-0.5 * ((x[None, :] - mus[:, None]) ** 2) / vars_[:, None]
                - 0.5 * np.log(2 * np.pi * vars_[:, None])
                + np.log(np.maximum(pis[:, None], 1e-300)))
        logp -= logp.max(axis=0, keepdims=True)
        r = np.exp(logp)
        r /= np.maximum(r.sum(axis=0, keepdims=True), 1e-300)
        nk = np.maximum(r.sum(axis=1), 1e-12)
        mus = (r @ x) / nk
        vars_ = np.maximum((r @ (x ** 2)) / nk - mus ** 2, floor)
        pis = nk / n
    order = np.argsort(mus)
    return pis[order], mus[order], np.sqrt(vars_[order])


_SINGLETONS = {"normal": Normal(), "lognormal": LogNormal(),
               "drift": Drift(0.0)}


def get_family(family) -> ChannelFamily:
    """Accept a family name or a ChannelFamily instance; return the instance."""
    if isinstance(family, ChannelFamily):
        return family
    if family is None:
        return _SINGLETONS["normal"]
    if isinstance(family, str):
        if family == "empirical":
            raise ValueError("the empirical family carries fitted parameters; "
                             "build it with Empirical.from_samples(...) "
                             "instead of the bare name")
        if family == "defective":
            raise ValueError("the defective family carries failure "
                             "probabilities; build it with Defective(p, "
                             "pricing=...) instead of the bare name")
        if family in _SINGLETONS:
            return _SINGLETONS[family]
        raise ValueError(f"unknown family {family!r}; expected one of "
                         f"{FAMILIES} or a ChannelFamily instance")
    if isinstance(family, dict):  # state_dict round-trip
        d = dict(family)
        dist = d.pop("dist_id")
        if dist == "drift":
            return Drift(np.asarray(d["rho"], np.float32))
        if dist == "empirical":
            return Empirical(np.asarray(d["weights"]), np.asarray(d["means"]),
                             np.asarray(d["stds"]))
        if dist == "defective":
            return Defective(np.asarray(d["p"], np.float32),
                             pricing=float(d.get("lam", 1.0)))
        return _SINGLETONS[dist]
    raise TypeError(f"cannot interpret {type(family).__name__} as a family")


def resolve_family(family, k: int) -> Tuple[str, np.ndarray]:
    """Lower a family spec to the kernel-facing ``(dist_id, extra (E,K))``.

    Accepts a family name, a ChannelFamily instance, a state_dict, or an
    already-lowered ``(dist_id, extra)`` pair — the latter passes traced
    ``extra`` arrays straight through, which is what jitted solvers use to
    avoid retracing when only the family parameters move. A pre-lowered
    ``extra`` may also be the per-row (E, F, K) stack (each candidate row
    its own fleet — the workflow solver's stage axis).
    """
    if isinstance(family, tuple) and len(family) == 2:
        dist_id, extra = family
        _check_dist(dist_id)
        shape = tuple(extra.shape)
        ok2 = shape == (extra_rows(dist_id), k)
        ok3 = (len(shape) == 3 and shape[0] == extra_rows(dist_id)
               and shape[2] == k)
        if not (ok2 or ok3):
            raise ValueError(f"extra for {dist_id!r} must be "
                             f"({extra_rows(dist_id)}, {k}) or "
                             f"({extra_rows(dist_id)}, F, {k}), got {shape}")
        return dist_id, extra
    fam = get_family(family)
    return fam.dist_id, fam.extra(k)


def family_from_extra(dist_id: str, extra) -> ChannelFamily:
    """Raise a lowered ``(dist_id, extra (E, K))`` pair back to a
    ChannelFamily instance — the inverse of :func:`resolve_family` for
    concrete (non-traced) extras. Used by layers that transform the lowered
    parameters (e.g. the sunk-work remaining-stats rescaling) and then need
    a family object for API boundaries that validate specs (Stage, checks)."""
    _check_dist(dist_id)
    ex = np.asarray(extra, np.float32)
    if dist_id == "normal":
        return _SINGLETONS["normal"]
    if dist_id == "lognormal":
        return _SINGLETONS["lognormal"]
    if dist_id == "drift":
        return Drift(ex[0])
    if dist_id == "defective":
        lam = float(ex[1].flat[0]) if ex[1].size else 1.0
        return Defective(np.clip(ex[0], 0.0, 1.0), pricing=lam)
    C = EMP_COMPONENTS
    return Empirical(ex[0:C], ex[C:2 * C], ex[2 * C:3 * C])


def remaining_work_stats(dist_id: str, mus, sigmas, extra, done):
    """Channel statistics for the *remaining* work after sunk progress.

    The mid-flight re-solve contract (host-side, numpy): ``done`` is the
    per-channel work fraction already completed, ``r = max(1 - sum(done), 0)``
    the total remaining work, and the re-solve optimizes a fresh unit simplex
    over statistics rescaled so that assigning remaining-share ``w'`` means
    executing ``w' * r`` units of original work:

    * scale families (normal, lognormal, defective, empirical): completion
      time of ``s`` units is ``s``-linear, so ``(mu, sigma) -> (r mu,
      r sigma)`` (mixture rows likewise); shape parameters (``p``, ``lam``,
      mixture weights) are per-attempt physics and do not rescale.
    * drift: a channel that already executed ``d_k`` units sits at inflated
      instantaneous rate ``mu (1 + rho d_k)``; the residual completion time
      of ``s`` more units is ``N(s mu (1 + rho d_k)(1 + rho' s/2),
      (s sigma)^2)`` with ``rho' = rho / (1 + rho d_k)``. Substituting
      ``s = w' r`` gives ``mu' = r mu (1 + rho d_k)``, ``sigma' = r sigma``,
      ``rho'' = rho r / (1 + rho d_k)``.

    Returns ``(mus_r, sigmas_r, extra_r, r)`` as float64 numpy arrays plus
    the scalar remaining fraction. ``r == 0`` returns all-zero stats — the
    caller should short-circuit (nothing left to solve).
    """
    _check_dist(dist_id)
    mus = np.asarray(mus, np.float64)
    sigmas = np.asarray(sigmas, np.float64)
    extra = np.asarray(extra, np.float64)
    done = np.asarray(done, np.float64)
    if done.shape != mus.shape:
        raise ValueError(f"done must be per-channel {mus.shape}, "
                         f"got {done.shape}")
    if done.size and (float(done.min()) < -1e-9
                      or float(done.sum()) > 1.0 + 1e-6):
        raise ValueError("done fractions must be nonnegative with total "
                         f"<= 1, got sum {float(done.sum()):.6f}, "
                         f"min {float(done.min()):.3e}")
    r = float(max(1.0 - done.sum(), 0.0))
    extra_r = extra.copy()
    if dist_id == "drift":
        rho = extra[0]
        inflate = 1.0 + rho * done
        mus_r = r * mus * inflate
        sigmas_r = r * sigmas
        extra_r[0] = rho * r / np.maximum(inflate, 1e-12)
        return mus_r, sigmas_r, extra_r, r
    if dist_id == "empirical":
        C = EMP_COMPONENTS
        extra_r[C:3 * C] *= r  # component means and stds scale; weights don't
        return r * mus, r * sigmas, extra_r, r
    # normal / lognormal / defective: pure scale families, shape params fixed
    return r * mus, r * sigmas, extra_r, r
