"""Completion-time moments of a split.

For a split ``w`` of one stage's work over K channels, channel k finishes
at ``T_k(w_k)``, and the stage at ``max_k T_k``. The families:

* ``normal``:    ``T_k ~ N(w mu, (w sigma)^2)``;
* ``lognormal``: ``T_k = w R`` with ``R`` log-normal of mean ``mu`` and
  standard deviation ``sigma``;
* ``drift``:     ``T_k ~ N(mu w (1 + rho w / 2), (w sigma)^2)``.

The stage's mean and variance are the survival integrals
``E[M] = int_0^tmax (1 - F(t)) dt`` and ``E[M^2] = 2 int_0^tmax t (1 - F(t)) dt``
with ``F = prod_k P(T_k <= t)``, taken by the trapezoid rule on ``num_t``
equally spaced points of ``[0, tmax]``, where ``tmax`` is the largest
``mean_k + 10 std_k``. A channel with no work or no spread is a point mass
at its mean. This is the quadrature the scheduler prices splits with
(arXiv:1507.00391, section 3, with a grid in place of the closed form), so
the reference and the program compute the same number and differ only by
rounding. ``dtype`` chooses the precision: float64 is the reference,
bfloat16 the control.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtr

REACH_Z = 10.0       # integration reach, in standard deviations
CDF_FLOOR = 1e-37    # log-CDF clamp, a normal number in float32 and bfloat16
TINY = 1e-20         # log floor of the time grid (t = 0)


def _phi_cdf(z):
    """Standard normal CDF in ``z``'s dtype (bfloat16 rounds a float32 one)."""
    if z.dtype in (jnp.float64, jnp.float32):
        return ndtr(z)
    return ndtr(z.astype(jnp.float32)).astype(z.dtype)


def _moments(w, mu, sigma, rho, mask, *, family, num_t):
    """(mean, variance) of one row's completion time; arrays are (K,)."""
    dt = w.dtype
    if family == "drift":
        mean = mu * w * (1.0 + 0.5 * rho * w)
    else:
        mean = w * mu
    std = w * sigma
    reach = jnp.where(mask, mean + REACH_Z * std, 0.0)
    tmax = jnp.maximum(jnp.max(reach), jnp.asarray(1e-12, dt))
    ts = tmax * jnp.linspace(0.0, 1.0, num_t, dtype=dt)
    t = ts[:, None]
    if family == "lognormal":
        ok = mask & (w > 0) & (sigma > 0) & (mu > 0)
        safe_mu = jnp.where(mu > 0, mu, 1.0)
        s2 = jnp.log1p(jnp.square(sigma / safe_mu))
        s = jnp.sqrt(s2)
        base = jnp.log(safe_mu) - 0.5 * s2
        z = ((jnp.log(jnp.maximum(t, jnp.asarray(TINY, dt)))
              - jnp.log(jnp.where(w > 0, w, 1.0)) - base)
             / jnp.where(ok, s, 1.0))
    elif family in ("normal", "drift"):
        ok = mask & (std > 0)
        z = (t - mean) / jnp.where(ok, std, 1.0)
    else:
        raise ValueError(f"no reference for family {family!r}")
    cdf = jnp.where(ok, _phi_cdf(z), (t >= mean).astype(dt))
    cdf = jnp.where(mask, cdf, 1.0)
    log_f = jnp.sum(jnp.log(jnp.clip(cdf, jnp.asarray(CDF_FLOOR, dt), 1.0)),
                    axis=-1)
    surv = 1.0 - jnp.exp(log_f)
    h = tmax / (num_t - 1)
    m1 = (jnp.sum(surv) - 0.5 * (surv[0] + surv[-1])) * h
    ts_surv = ts * surv
    m2 = 2.0 * (jnp.sum(ts_surv) - 0.5 * (ts_surv[0] + ts_surv[-1])) * h
    return m1, jnp.maximum(m2 - m1 * m1, 0.0)


@partial(jax.jit, static_argnames=("family", "num_t"))
def stage_moments(W, mus, sigmas, rho, mask, *, family, num_t):
    """(mu, var) per row of ``W`` (rows, K), one row at a time so that the
    (T, K) grid of only one row is held."""
    fn = partial(_moments, family=family, num_t=num_t)
    return jax.lax.map(lambda a: fn(*a), (W, mus, sigmas, rho, mask))


def as_dtype(arrays, dtype):
    """Host arrays to device arrays of ``dtype`` (masks stay boolean)."""
    return [jnp.asarray(a) if np.asarray(a).dtype == bool
            else jnp.asarray(np.asarray(a, np.float64), dtype)
            for a in arrays]
