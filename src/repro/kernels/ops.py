"""Dispatch for ``frontier_grid``, the candidate-split moment evaluation.

:func:`frontier_moments` (forward, differentiable through a custom VJP) and
:func:`frontier_moments_with_grads` (one fused launch with the analytic
adjoints) are how every solver, the balancer and the serving engine
evaluate splits. ``impl`` picks the backend:

    "xla"              — pure-jnp reference path (``ref.frontier_grid_ref``
                         streamed through ``lax.map``; the portable fallback)
    "pallas"           — compiled Pallas TPU kernel (real-hardware path)
    "pallas_interpret" — Pallas kernel body executed in Python (CPU validation)

The wrappers own padding/shape glue and resolve the launch shape through
``kernels.autotune``, so the kernel can assume aligned shapes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..analysis import sanitize as _san
from ..obs import events as _obs_events
from ..obs import names as _obs_names
from ..obs import trace as _obs
from . import autotune as _at
from . import frontier_grid as _fg
from . import ref

__all__ = ["frontier_moments", "frontier_moments_with_grads", "IMPLS"]

IMPLS = ("xla", "pallas", "pallas_interpret")


def _check(impl: str) -> None:
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")


def _resolve_block_f(F: int, K: int, num_t: int, impl: str,
                     block_f: Optional[int], fused: bool,
                     dist_id: str = "normal", params: bool = False,
                     stacked: bool = False) -> int:
    """Explicit block_f wins; otherwise consult the autotune cache/model."""
    if block_f is not None:
        return max(min(block_f, F), 1)
    return _at.lookup(F, K, num_t, backend=impl, fused=fused, dist_id=dist_id,
                      params=params, stacked=stacked)


def _resolve_family(family, K: int):
    """Lower a family spec to (static dist_id, traced extra array).

    ``extra`` is (E, K) for a shared fleet, or (E, F, K) when the caller
    pre-lowered a per-row stack (the workflow solver's stage axis)."""
    from repro.core.distributions import resolve_family

    dist_id, extra = resolve_family(family, K)
    return dist_id, jnp.asarray(extra, jnp.float32)


def _stack_extra(extra, F: int):
    """Lift a shared (E, K) extra to the per-row (E, F, K) layout."""
    if extra.ndim == 3:
        return extra
    return jnp.broadcast_to(extra[:, None, :],
                            (extra.shape[0], F, extra.shape[1]))


# repro: allow[RPA001] layout-only padding glue: never evaluates a CDF, the
# family rides in the sibling dist_id argument of every caller
def _pad_rows(pad, W, mus, sigmas, extra):
    """Pad the candidate axis with copies of row 0 (sliced off after).

    Per-row statistics (mus.ndim == 2) ride the same padding so padded rows
    stay self-consistent (they recompute row 0's stage under row 0's fleet).
    """
    W = jnp.concatenate([W, jnp.tile(W[:1], (pad, 1))], 0)
    if mus.ndim == 2:
        mus = jnp.concatenate([mus, jnp.tile(mus[:1], (pad, 1))], 0)
        sigmas = jnp.concatenate([sigmas, jnp.tile(sigmas[:1], (pad, 1))], 0)
        extra = jnp.concatenate(
            [extra, jnp.tile(extra[:, :1], (1, pad, 1))], 1)
    return W, mus, sigmas, extra


# repro: allow[RPA001] layout-only chunking glue: reshapes stat tiles for
# lax.map, family dispatch happens in the per-block ref call of the caller
def _row_blocks(bf, W, mus, sigmas, extra):
    """Reshape aligned rows into lax.map blocks + a per-block ref thunk."""
    K = W.shape[1]
    if mus.ndim == 2:
        # stats chunk alongside W; extra goes (E, F, K) -> (nb, bf, E, K)
        xs = (W.reshape(-1, bf, K), mus.reshape(-1, bf, K),
              sigmas.reshape(-1, bf, K),
              jnp.moveaxis(extra, 0, 1).reshape(-1, bf, extra.shape[0], K))
        unpack = lambda b: (b[0], b[1], b[2], jnp.moveaxis(b[3], 1, 0))
    else:
        xs = (W.reshape(-1, bf, K),)
        unpack = lambda b: (b[0], mus, sigmas, extra)
    return xs, unpack


def _moments_fwd(W, mus, sigmas, extra, num_t, impl, bf, z, dist_id):
    """Forward-only batched moments on aligned shapes (bf resolved)."""
    F = W.shape[0]
    pad = (-F) % bf
    if impl == "xla":
        if F <= bf:
            return ref.frontier_grid_ref(W, mus, sigmas, num_t=num_t, z=z,
                                         dist_id=dist_id, extra=extra)
        if pad:
            W, mus, sigmas, extra = _pad_rows(pad, W, mus, sigmas, extra)
        xs, unpack = _row_blocks(bf, W, mus, sigmas, extra)

        def block(b):
            wb, mb, sb, eb = unpack(b)
            return ref.frontier_grid_ref(wb, mb, sb, num_t=num_t, z=z,
                                         dist_id=dist_id, extra=eb)

        mu, var = jax.lax.map(block, xs)
        return mu.reshape(-1)[:F], var.reshape(-1)[:F]
    if pad:
        W, mus, sigmas, extra = _pad_rows(pad, W, mus, sigmas, extra)
    mu, var = _fg.frontier_grid(W, mus, sigmas, extra, num_t=num_t, z=z,
                                block_f=bf, dist_id=dist_id,
                                interpret=(impl == "pallas_interpret"))
    return mu[:F], var[:F]


def _moments_grads(W, mus, sigmas, extra, num_t, impl, bf, z, dist_id,
                   param_grads: bool = False):
    """Fused (mu, var, dmu_dW, dvar_dW[, param adjoints]) on aligned shapes.

    ``param_grads`` switches both backends to the full-parameter launch: six
    extra (F, K) outputs (mus/sigmas/extra-row-0 adjoints of both moments) —
    still ONE kernel launch on the Pallas paths.
    """
    F = W.shape[0]
    pad = (-F) % bf
    if impl == "xla":
        if F <= bf:
            return ref.frontier_grid_with_grads_ref(
                W, mus, sigmas, num_t=num_t, z=z, dist_id=dist_id,
                extra=extra, param_grads=param_grads)
        if pad:
            W, mus, sigmas, extra = _pad_rows(pad, W, mus, sigmas, extra)
        xs, unpack = _row_blocks(bf, W, mus, sigmas, extra)

        def block(b):
            wb, mb, sb, eb = unpack(b)
            return ref.frontier_grid_with_grads_ref(
                wb, mb, sb, num_t=num_t, z=z, dist_id=dist_id,
                extra=eb, param_grads=param_grads)

        outs = jax.lax.map(block, xs)
        K = W.shape[1]
        return tuple(o.reshape(-1)[:F] if o.ndim == 2
                     else o.reshape(-1, K)[:F] for o in outs)
    if pad:
        W, mus, sigmas, extra = _pad_rows(pad, W, mus, sigmas, extra)
    outs = _fg.frontier_grid_with_grads(
        W, mus, sigmas, extra, num_t=num_t, z=z, block_f=bf, dist_id=dist_id,
        interpret=(impl == "pallas_interpret"), param_grads=param_grads)
    return tuple(o[:F] for o in outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _frontier_moments_vjp(W, mus, sigmas, extra, num_t, impl, bfs, z, dist_id):
    return _moments_fwd(W, mus, sigmas, extra, num_t, impl, bfs[0], z, dist_id)


def _frontier_moments_vjp_fwd(W, mus, sigmas, extra, num_t, impl, bfs, z,
                              dist_id):
    # bfs = (forward block_f, pgrad block_f): the full-parameter fused launch
    # holds ~4x the accumulators, so a forward-tuned block can overflow its
    # budget. The VJP's forward pass runs the param_grads kernel — one launch
    # yields every residual the backward needs, W and channel-statistic
    # adjoints alike (the closed estimation loop's differentiation surface).
    outs = _moments_grads(W, mus, sigmas, extra, num_t, impl, bfs[1], z,
                          dist_id, param_grads=True)
    mu, var, dmu, dvar, dmu_m, dvar_m, dmu_s, dvar_s, dmu_e, dvar_e = outs
    return (mu, var), (dmu, dvar, dmu_m, dvar_m, dmu_s, dvar_s,
                       dmu_e, dvar_e, extra)


def _frontier_moments_vjp_bwd(num_t, impl, bfs, z, dist_id, res, cts):
    (dmu, dvar, dmu_m, dvar_m, dmu_s, dvar_s, dmu_e, dvar_e, extra) = res
    g_mu, g_var = cts
    dW = g_mu[:, None] * dmu + g_var[:, None] * dvar
    if extra.ndim == 3:
        # per-row statistics (the stage-stacked layout): every row owns its
        # fleet, so the cotangents stay per-row — no cross-row reduction
        d_mus = g_mu[:, None] * dmu_m + g_var[:, None] * dvar_m
        d_sigmas = g_mu[:, None] * dmu_s + g_var[:, None] * dvar_s
        d_extra = jnp.zeros_like(extra)
        d_extra = d_extra.at[0].set(g_mu[:, None] * dmu_e
                                    + g_var[:, None] * dvar_e)
        return dW, d_mus, d_sigmas, d_extra
    # channel statistics are shared across candidate rows: sum the per-row
    # adjoints against the output cotangents
    d_mus = g_mu @ dmu_m + g_var @ dvar_m
    d_sigmas = g_mu @ dmu_s + g_var @ dvar_s
    # extra cotangent: row 0 carries the differentiable shape parameter
    # (drift's rho, defective's failure probability p); remaining rows (the
    # defective pricing constant lam, the empirical mixture parameters, and
    # all rows for the other families) are solve constants with zero
    # cotangent by contract
    d_extra = jnp.zeros_like(extra)
    d_extra = d_extra.at[0].set(g_mu @ dmu_e + g_var @ dvar_e)
    return dW, d_mus, d_sigmas, d_extra


_frontier_moments_vjp.defvjp(_frontier_moments_vjp_fwd,
                             _frontier_moments_vjp_bwd)


def frontier_moments(W, mus, sigmas, *, num_t: int = 1024, impl: str = "xla",
                     block_f: Optional[int] = None, z: float = 10.0,
                     family="normal"):
    """Batched (mu, var) over candidate splits W: (F, K).

    The single entry point for candidate-split moment evaluation: the frontier
    tracers, the PGD objective, the balancer tick and the fleet benchmarks all
    route here. ``family`` selects the per-channel completion-time
    distribution — a name in {normal, lognormal, drift} or a
    ``core.distributions.ChannelFamily`` instance (Drift with per-channel
    rates, a fitted Empirical mixture, Defective with per-channel failure
    probabilities); it lowers to a static ``dist_id`` so
    each family compiles to its own specialized kernel. F is padded to a
    ``block_f`` multiple internally (padding rows repeat row 0 and are sliced
    off), so callers never see the kernel's divisibility requirement. When
    ``block_f`` is None the launch shape is resolved through
    ``kernels.autotune`` (VMEM-budget model + cached sweep results, keyed by
    family). The "xla" path streams candidates through lax.map over
    ``block_f``-row blocks, bounding peak memory at O(block_f * num_t * K)
    instead of materializing the full (F, T, K) intermediate — that is what
    lets a K=1024 x F=4096 tick run at all.

    Differentiable on every impl via a registered ``jax.custom_vjp`` that
    backprops through the analytic adjoint of the (family-parametric)
    survival integral (see ``frontier_grid.py``) instead of
    autodiff-replaying the quadrature — in the split weights ``W`` AND in
    the channel statistics: ``mus``, ``sigmas`` and, for the drift and
    defective families, ``extra`` row 0 (per-channel ``rho`` / failure
    probability ``p``) all receive nonzero analytic cotangents, which is what lets ``core.sensitivity`` chain the solve
    through the NIG posterior parameters (the closed estimation loop of
    arXiv:1511.00613). The empirical family's mixture parameters remain
    solve constants (re-fit from data, never descended): their cotangents
    are zero by contract.

    Stage-stacked layout: ``mus``/``sigmas`` may also be (F, K) — each
    candidate row carries its OWN channel fleet (and the family's ``extra``
    may be (E, F, K) per-row). This is what lets the workflow subsystem
    evaluate every stage of a DAG — different fleets, one family — as rows
    of a single launch instead of a per-stage Python loop over kernel
    launches. A shared (E, K) ``extra`` combined with per-row mus/sigmas is
    broadcast to the per-row layout here. The VJP keeps the per-row
    cotangent structure (no cross-row reduction for per-row statistics).
    """
    _check(impl)
    W = jnp.asarray(W, jnp.float32)
    mus = jnp.asarray(mus, jnp.float32)
    sigmas = jnp.asarray(sigmas, jnp.float32)
    F, K = W.shape
    dist_id, extra = _resolve_family(family, K)
    _san.check_frontier_inputs(W, mus, sigmas, extra, dist_id=dist_id)
    stacked = mus.ndim == 2
    if stacked:
        extra = _stack_extra(extra, F)
    # resolve BOTH launch shapes up front: the primal runs the forward
    # kernel, but under jax.grad the VJP's forward pass runs the fused
    # full-parameter one, whose working set is ~4x larger (smaller safe
    # block_f). An explicit block_f binds the forward launch verbatim; the
    # fused launch it implies is still clamped by the budget model — the
    # caller sized the block they asked for, not the 4x-bigger one
    # differentiation swaps in.
    bf_fwd = _resolve_block_f(F, K, num_t, impl, block_f, fused=False,
                              dist_id=dist_id, stacked=stacked)
    trace_on = _obs.enabled()
    at_out = None
    if trace_on:
        at_out = "explicit" if block_f is not None else _at.last_outcome()
    bf_fused = _resolve_block_f(F, K, num_t, impl, None, fused=True,
                                dist_id=dist_id, params=True, stacked=stacked)
    if block_f is not None:
        bf_fused = min(max(min(block_f, F), 1), bf_fused)
    if trace_on:
        # span only on concrete (host-side) launches: recording at trace
        # time would log once per COMPILE, not per launch, and the tracer
        # must never plant effects inside a traced computation — a tracer
        # hit is logged as a compile audit event instead
        if _san.all_concrete(W, mus, sigmas, extra):
            with _obs.span(_obs_names.SPAN_KERNEL_LAUNCH, family=dist_id,
                           mode="fwd", F=F, K=K, num_t=num_t,
                           block_f=bf_fwd, impl=impl, stacked=stacked,
                           autotune=at_out):
                return _frontier_moments_vjp(W, mus, sigmas, extra, num_t,
                                             impl, (bf_fwd, bf_fused), z,
                                             dist_id)
        _obs_events.kernel_compile("fwd", F, K, num_t, impl)
    return _frontier_moments_vjp(W, mus, sigmas, extra, num_t, impl,
                                 (bf_fwd, bf_fused), z, dist_id)


def frontier_moments_with_grads(W, mus, sigmas, *, num_t: int = 1024,
                                impl: str = "xla",
                                block_f: Optional[int] = None,
                                z: float = 10.0, family="normal",
                                param_grads: bool = False):
    """Fused (mu, var, dmu_dW, dvar_dW) over candidate splits W: (F, K).

    One launch returns the moments and their analytic adjoints w.r.t. every
    split weight — what the PGD solver consumes directly each step (no
    autodiff replay, no second launch). ``param_grads=True`` widens the same
    launch to the full-parameter adjoint 10-tuple

        (mu, var, dmu_dW, dvar_dW, dmu_dmus, dvar_dmus,
         dmu_dsigmas, dvar_dsigmas, dmu_dex, dvar_dex)

    (``d*_dex`` = extra row 0, drift's ``rho`` or defective's ``p``;
    zeros for other families) —
    the surface ``core.sensitivity`` and the posterior-sensitivity analysis
    consume. Family/padding/autotune glue matches :func:`frontier_moments`,
    including the stage-stacked per-row statistics layout (``mus``/``sigmas``
    (F, K), ``extra`` (E, F, K)) the workflow solver's joint PGD consumes;
    the two gradient modes autotune independently (``grad`` vs ``pgrad``
    cache keys — the parameter mode's working set is larger).
    """
    _check(impl)
    W = jnp.asarray(W, jnp.float32)
    mus = jnp.asarray(mus, jnp.float32)
    sigmas = jnp.asarray(sigmas, jnp.float32)
    dist_id, extra = _resolve_family(family, W.shape[1])
    _san.check_frontier_inputs(W, mus, sigmas, extra, dist_id=dist_id)
    stacked = mus.ndim == 2
    if stacked:
        extra = _stack_extra(extra, W.shape[0])
    bf = _resolve_block_f(W.shape[0], W.shape[1], num_t, impl, block_f,
                          fused=True, dist_id=dist_id, params=param_grads,
                          stacked=stacked)
    if _obs.enabled():
        mode = "pgrad" if param_grads else "grad"
        if _san.all_concrete(W, mus, sigmas, extra):
            at_out = ("explicit" if block_f is not None
                      else _at.last_outcome())
            with _obs.span(_obs_names.SPAN_KERNEL_LAUNCH, family=dist_id,
                           mode=mode, F=int(W.shape[0]), K=int(W.shape[1]),
                           num_t=num_t, block_f=bf, impl=impl,
                           stacked=stacked, autotune=at_out):
                return _moments_grads(W, mus, sigmas, extra, num_t, impl,
                                      bf, z, dist_id,
                                      param_grads=param_grads)
        _obs_events.kernel_compile(mode, int(W.shape[0]), int(W.shape[1]),
                                   num_t, impl)
    return _moments_grads(W, mus, sigmas, extra, num_t, impl, bf, z, dist_id,
                          param_grads=param_grads)

