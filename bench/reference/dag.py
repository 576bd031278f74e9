"""Makespan of a stage DAG from its stages' completion-time moments.

A stage starts when all its predecessors have finished, so a node's finish
time is the maximum of its predecessors' finish times plus its own
duration: series edges add means and variances, and a join takes the
maximum. The maximum of two independent normal variables has the exact
first two moments of Clark (1961, "The greatest of a finite set of random
variables", Operations Research 9(2)); a join of more than two folds them
one predecessor at a time, in the order the DAG lists them, matching each
running maximum to a normal. The sink's finish time is the makespan.

:func:`mc_makespan` samples the same DAG directly, with no normal matching,
as an oracle for how far that approximation carries.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .frontier import _phi_cdf


def _clark(m1, v1, m2, v2, dtype):
    s1 = jnp.sqrt(jnp.maximum(jnp.asarray(v1, dtype), 1e-18))
    s2 = jnp.sqrt(jnp.maximum(jnp.asarray(v2, dtype), 1e-18))
    m1, m2 = jnp.asarray(m1, dtype), jnp.asarray(m2, dtype)
    a = jnp.sqrt(s1 * s1 + s2 * s2)
    alpha = (m1 - m2) / a
    cdf = _phi_cdf(alpha)
    pdf = jnp.exp(-0.5 * alpha * alpha) / jnp.asarray(np.sqrt(2 * np.pi), dtype)
    e1 = m1 * cdf + m2 * (1.0 - cdf) + a * pdf
    e2 = ((m1 * m1 + s1 * s1) * cdf + (m2 * m2 + s2 * s2) * (1.0 - cdf)
          + (m1 + m2) * a * pdf)
    return e1, jnp.maximum(e2 - e1 * e1, 0.0)


def _order(names, edges):
    preds = {n: [u for u, v in edges if v == n] for n in names}
    done, order = set(), []
    while len(order) < len(names):
        for n in names:
            if n not in done and all(p in done for p in preds[n]):
                order.append(n)
                done.add(n)
    return order, preds


def makespan(names, edges, stage_mu, stage_var, dtype=jnp.float64):
    """(mean, variance) of the makespan; ``stage_*`` in ``names`` order."""
    idx = {n: i for i, n in enumerate(names)}
    order, preds = _order(names, edges)
    fin = {}
    for n in order:
        ps = preds[n]
        if not ps:
            m, v = jnp.asarray(0.0, dtype), jnp.asarray(0.0, dtype)
        else:
            m, v = fin[ps[0]]
            for p in ps[1:]:
                m, v = _clark(m, v, *fin[p], dtype)
        fin[n] = (m + jnp.asarray(stage_mu[idx[n]], dtype),
                  v + jnp.asarray(stage_var[idx[n]], dtype))
    sinks = [n for n in names if not any(u == n for u, _ in edges)]
    m, v = fin[sinks[0]]
    for s in sinks[1:]:
        m, v = _clark(m, v, *fin[s], dtype)
    return float(m), float(v)


def mc_makespan(names, edges, stats, weights, trials, rng):
    """Sampled makespan mean over ``trials`` runs of the DAG.

    ``stats[name] = (family, mus, sigmas, rho)``; ``weights[name]`` the
    stage's split. Channel durations are drawn from the family's law.
    """
    order, preds = _order(names, edges)
    fin = {}
    for n in order:
        family, mus, sigmas, rho = stats[n]
        w = np.asarray(weights[n], np.float64)
        z = rng.standard_normal((trials, w.size))
        if family == "lognormal":
            s2 = np.log1p((sigmas / mus) ** 2)
            t = w * np.exp(np.log(mus) - 0.5 * s2 + np.sqrt(s2) * z)
        else:
            mean = mus * w * (1.0 + 0.5 * rho * w) if family == "drift" \
                else mus * w
            t = mean + w * sigmas * z
        t = np.where(w > 0, t, 0.0).max(axis=1)
        start = (np.max([fin[p] for p in preds[n]], axis=0) if preds[n]
                 else 0.0)
        fin[n] = start + t
    sinks = [n for n in names if not any(u == n for u, _ in edges)]
    return float(np.max([fin[s] for s in sinks], axis=0).mean())
