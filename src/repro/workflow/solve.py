"""Joint optimization of every stage split in a StageDAG.

The greedy baseline solves each stage alone (fastest expected stage time)
and composes whatever comes out. That is exactly what the paper shows to be
insufficient WITHIN a stage — variance matters at a join — lifted one level:
a stage feeding a join should trade a little expected time for variance,
because the join's ``E[max]`` pays for every branch's spread, and the only
way to see that is to optimize the end-to-end makespan through the
composition.

This solver does that with one batched kernel path:

1. **Stack**: every stage's iterate is one row of a ``(R*S, K_max)`` weight
   matrix (R = multi-starts, S = stages; stage fleets zero-padded to
   ``K_max`` — a ``w=0`` channel is a point mass that drops out of the
   survival product, so padding is exact, and a mask keeps padded weights at
   zero through the projection). Stages are grouped by completion-time
   family (``dist_id`` is a static kernel specialization); within a group
   every stage's statistics ride the per-row (stacked) layout of
   ``ops.frontier_moments_with_grads``, so ONE fused launch per family —
   not per stage — returns every stage's moments and analytic adjoints.
   An all-one-family DAG (the benchmark) is literally a single launch per
   PGD step. A single-channel stage's split is w = [1] whatever the
   iterate, so it is no PGD row: its moments come from one launch per
   phase, at its own width of one channel, and enter the composition as
   constants. The PGD rows, their projection and their launches hold only
   the stages whose split can move, padded to the widest of them.
2. **Compose**: the per-stage ``(mu_s, var_s)`` flow through
   ``dag.compose_moments`` (series sums + Clark joins) to the makespan;
   autodiff runs only over these O(S) Clark folds — the expensive
   d(moments)/dW part is the fused kernel adjoints (PR 2/4), chained by
   hand: ``dL/dW_s = dL/dmu_s * dmu_s/dW_s + dL/dvar_s * dvar_s/dW_s``.
3. **Descend**: projected gradient on the concatenation of all stage
   simplices (masked Held projection per stage block), cosine step decay,
   multi-start, warm-startable from a previous solve (the balancer's tick
   path).

**Multi-fidelity ladder (PR 8).** Quadrature resolution is the solve's
price knob, and most of the work does not need the fine rung:

* the stage-local presolve and the candidate triage run at a coarse
  ``presolve_num_t`` (default 128 points — the composed-makespan RANKING of
  candidates is far less sensitive to quadrature than the absolute moments,
  because the coarse/fine bias is shared across candidates);
* starts whose coarse composed score trails the coarse incumbent by more
  than ``prune_margin`` (relative) are dropped before any fine-fidelity
  work, and near-duplicate survivors (starts that presolved to the same
  frontier point) collapse to their best-scored representative —
  typically the refine descends one survivor, not every start;
* the composed refine runs at ``num_t`` under a plateau early-stop
  (``plateau_tol``/``plateau_patience``) instead of a fixed step count;
* the FINAL pick always scores the surviving candidate pool at evaluation
  resolution (``eval_num_t``, default max(num_t, 2048)) — coarse scores
  are triage-only and never decide the returned split.

**Incremental re-solves.** ``dirty`` names the stages whose estimation
state moved since the ``warm_start`` split was computed: only their rows
take PGD steps (a traced 0/1 mask gates the update — frozen rows still
contribute their moments to the composed makespan but pass through every
step and the final pick BITWISE, never re-projected or renormalized). An
empty dirty set short-circuits to the warm split verbatim with one forward
evaluation and no PGD launch at all.

Objective: ``makespan_mu + lam_var * makespan_var``; with ``risk_lam > 0``
and per-stage NIG posteriors, finalists additionally pay the delta-method
fragility of the predicted makespan under estimation error — the
``core.sensitivity`` machinery chained through the composition (the stage
parameter adjoints come from the same stacked full-parameter launch).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import sanitize as _san
from ..core.bayes import nig_estimate_ses
from ..core.distributions import resolve_family
from ..core.partitioner import optimize_weights
from ..kernels import autotune, ops
from ..obs import names as obs_names
from ..obs import trace as obs
from .dag import StageDAG, compose_structure

__all__ = ["DAGDecision", "solve_dag", "solve_dag_greedy", "evaluate_dag",
           "stack_rows"]

# default coarse rung of the fidelity ladder: presolve + triage quadrature
_COARSE_NUM_T = 128
# refine steps start from a PRESOLVED (near-frontier) iterate, where the
# presolve's cold-start step size overshoots and oscillates for most of the
# cosine schedule — a 10x smaller step descends monotonically (which is also
# what makes the plateau early-stop a sound criterion for the refine)
_PRESOLVE_LR = 0.05
_REFINE_LR = 0.005
# triage survivors whose weight stacks agree within this L-inf distance are
# the SAME candidate (independent starts converged to one frontier point);
# refining duplicates is pure waste, the best-scored representative stays
_DEDUPE_TOL = 5e-3


@dataclass(frozen=True)
class DAGDecision:
    """All stage splits plus the predicted end-to-end moments."""

    weights: Dict[str, np.ndarray]  # per-stage simplex weights (K_s,)
    makespan_mu: float
    makespan_var: float
    stage_mu: np.ndarray            # (S,) per-stage duration means
    stage_var: np.ndarray           # (S,)
    method: str
    family_groups: int = 1          # kernel launches per moment evaluation
    fragility: Optional[float] = None
    profile: Optional[dict] = None  # per-phase wall times + solver counters

    @property
    def relative_fragility(self) -> Optional[float]:
        if self.fragility is None:
            return None
        return float(self.fragility / max(self.makespan_mu, 1e-12))


# --------------------------------------------------------------------- stack
@dataclass(frozen=True)
class _Group:
    """Stages sharing one dist_id: one stacked launch serves them all."""

    dist_id: str
    idx: Tuple[int, ...]            # stage indices (canonical stage order)
    mus: np.ndarray                 # (n, Kmax) zero-padded
    sigmas: np.ndarray              # (n, Kmax)
    extra: np.ndarray               # (E, n, Kmax)


def stack_rows(rows, kmax: Optional[int] = None
               ) -> Tuple[List[_Group], np.ndarray, int]:
    """Variable-shape row-block bookkeeping for stacked family launches.

    ``rows`` is any sequence of ``(mus, sigmas, family)`` triples — a DAG's
    stages, or a serving engine's live (instance, remaining-stage) pairs.
    Channel counts may differ per row; every row zero-pads its channel axis
    to ``kmax`` (a ``w=0`` channel is a point mass that drops out of the
    survival product, so padding is EXACT — the returned mask keeps padded
    weights at zero through the simplex projection). Rows group by lowered
    ``dist_id`` (a static kernel specialization) in first-appearance order,
    so one ``ops.frontier_moments*`` launch per group serves every row in
    it; ``group.idx`` indexes back into ``rows``.

    Pass ``kmax`` to pin the channel axis across calls: a serving tick
    whose live set changes shape every tick would otherwise re-jit per
    distinct max-K. Returns ``(groups, mask (N, kmax), kmax)``.
    """
    rows = list(rows)
    ks = [int(np.asarray(m).shape[0]) for m, _, _ in rows]
    kmax = max(ks) if kmax is None else int(kmax)
    if ks and max(ks) > kmax:
        raise ValueError(f"row channel count {max(ks)} exceeds the pinned "
                         f"kmax={kmax}")
    N = len(rows)
    mask = np.zeros((N, kmax), np.float32)
    by_dist: Dict[str, List[int]] = {}
    lowered = []
    for i, (mus_i, _, family) in enumerate(rows):
        dist_id, extra = resolve_family(family, ks[i])
        lowered.append((dist_id, np.asarray(extra, np.float32)))
        by_dist.setdefault(dist_id, []).append(i)
        mask[i, :ks[i]] = 1.0
    groups = []
    for dist_id, idx in by_dist.items():
        n = len(idx)
        E = lowered[idx[0]][1].shape[0]
        mus = np.zeros((n, kmax), np.float32)
        sgs = np.zeros((n, kmax), np.float32)
        ex = np.zeros((E, n, kmax), np.float32)
        for j, i in enumerate(idx):
            k = ks[i]
            mus[j, :k] = rows[i][0]
            sgs[j, :k] = rows[i][1]
            ex[:, j, :k] = lowered[i][1]
        groups.append(_Group(dist_id, tuple(idx), mus, sgs, ex))
    return groups, mask, kmax


def _stage_groups(dag: StageDAG) -> Tuple[List[_Group], np.ndarray, int]:
    """Group stages by family; returns (groups, mask (S, Kmax), Kmax)."""
    return stack_rows([(s.mus, s.sigmas, s.family) for s in dag.stages])


def _launch_args(groups):
    """``(dist_ids, idxs, stats)`` of stacked groups: the static launch
    specialization and the traced per-row statistics."""
    return (tuple(g.dist_id for g in groups), tuple(g.idx for g in groups),
            tuple((jnp.asarray(g.mus), jnp.asarray(g.sigmas),
                   jnp.asarray(g.extra)) for g in groups))


def _project_simplex_masked(v, mask):
    """Held projection onto the simplex of the ACTIVE (mask=1) channels.

    Inactive entries (a stage's zero-padding up to K_max) are pinned far
    below every active value so they never enter the threshold computation
    and land exactly on zero after the clamp.
    """
    k = v.shape[-1]
    vm = jnp.where(mask > 0, v, -1e9)
    u = jnp.sort(vm)[::-1]
    css = jnp.cumsum(u) - 1.0
    idx = jnp.arange(1, k + 1, dtype=v.dtype)
    cond = u - css / idx > 0
    rho = jnp.max(jnp.where(cond, jnp.arange(k), -1))
    theta = css[rho] / (rho + 1.0)
    return jnp.maximum(vm - theta, 0.0)


def _stage_moments_grads(W, dist_ids, idxs, stats, num_t, impl, bfs):
    """Per-stage (mu, var, dmu_dW, dvar_dW) — one stacked launch per family.

    W: (R, S, Kmax). Rows of group g are the R x n_g stage iterates; the
    group's per-stage statistics tile over starts in the same (r, j) order.
    """
    R, S, kmax = W.shape
    smu = jnp.zeros((R, S))
    svar = jnp.zeros((R, S))
    dmu = jnp.zeros((R, S, kmax))
    dvar = jnp.zeros((R, S, kmax))
    for g, dist_id in enumerate(dist_ids):
        idx = jnp.asarray(idxs[g])
        mus_g, sgs_g, ex_g = stats[g]
        n = mus_g.shape[0]
        rows = W[:, idx, :].reshape(R * n, kmax)
        m, v, dm, dv = ops.frontier_moments_with_grads(
            rows, jnp.tile(mus_g, (R, 1)), jnp.tile(sgs_g, (R, 1)),
            num_t=num_t, impl=impl, block_f=bfs[g],
            family=(dist_id, jnp.tile(ex_g, (1, R, 1))))
        smu = smu.at[:, idx].set(m.reshape(R, n))
        svar = svar.at[:, idx].set(v.reshape(R, n))
        dmu = dmu.at[:, idx, :].set(dm.reshape(R, n, kmax))
        dvar = dvar.at[:, idx, :].set(dv.reshape(R, n, kmax))
    return smu, svar, dmu, dvar


@partial(jax.jit, static_argnames=("structure", "dist_ids", "idxs", "steps",
                                   "patience", "num_t", "impl", "bfs",
                                   "composed", "sanitize", "fixed"))
def _pgd_phase(structure, dist_ids, idxs, stats, masks, W0, upd, lam_var,
               plateau_tol, steps: int, patience: int, num_t: int,
               impl: str, bfs, composed: bool, lr: float = _PRESOLVE_LR,
               warmup: int = 0, sanitize: bool = False, fixed=None,
               fixed_stats=()):
    """One masked-PGD phase over the stacked stage simplices.

    ``composed=False`` descends each stage's LOCAL expected join time (the
    graph-blind presolve objective — the per-row loss decouples into a sum
    of stage means); ``composed=True`` descends the composed makespan
    (fused kernel adjoints chained with the composition's cotangents).

    ``W0`` is the (R, S, Kmax) start stack of every stage. With ``fixed``
    (static ``(mov, fix, dist_ids, idxs, bfs)``) the stages ``fix`` are
    single-channel: one launch before the loop, over their family groups
    one channel wide (``fixed_stats``), gives their moments, which every
    step composes as constants, and the loop carries only the ``mov``
    rows, whose groups ``dist_ids``, ``idxs``, ``stats`` and ``masks``
    describe. ``fixed=None``: every stage is a row.

    ``upd`` is the traced (S,) dirty mask of an incremental re-solve: rows
    of frozen stages (``upd == 0``) contribute their moments to the
    composed objective but take no step — the update is gated by
    ``jnp.where`` so a frozen row passes through BITWISE (it is never
    re-projected; Held projection of an on-simplex point is not
    bit-stable). A traced mask means distinct dirty sets share one
    compiled solver.

    Plateau early-stop: the loop exits when the pool-best objective fails
    to improve by a relative ``plateau_tol`` for ``patience`` consecutive
    steps (``patience >= steps`` disables). Stalls only COUNT once the
    step index passes ``warmup``: a cold start under a large cosine step
    oscillates (the pool best can sit still for long windows while the
    iterates are mid-transit toward the real descent later in the
    schedule), so stall windows before the warmup are evidence of nothing.
    The cosine schedule keeps its ``steps``-length horizon, so early exit
    stops at a mid-schedule step size — the best-iterate tracking below
    makes that safe.

    Returns ``(W_final, W_best, best_loss, steps_run)``, the stacks of
    every stage (fixed rows as in ``W0``): ``W_best`` is the
    best-objective iterate seen per start at THIS phase's fidelity (the
    schedule can overshoot past it; both snapshots join the final pool so
    refinement can explore without ever losing ground).

    Static ``sanitize=True`` plants checkify invariant checks per step;
    legal only under ``analysis.sanitize.run_checked`` (see that module).
    """
    R, S = W0.shape[:2]
    W_mov = W0
    take = lambda g: g       # (R, S) cotangents -> those of the loop's rows
    if fixed is not None:
        mov, fix, f_ids, f_idxs, f_bfs = fixed
        W_mov, upd = W0[:, mov, :], upd[jnp.asarray(mov)]
        take = lambda g: g[:, mov]
        # the fixed rows' moments, constant through the phase (a grad
        # launch, so the kernel trace shows it as this phase's work)
        f_mu, f_var, _, _ = _stage_moments_grads(
            W0[:, fix, :1], f_ids, f_idxs, fixed_stats, num_t, impl, f_bfs)

        def full(m, f):      # (R, S_mov) moments and the constants -> (R, S)
            return jnp.zeros((R, S), m.dtype).at[:, mov].set(m) \
                .at[:, fix].set(f)

    proj = jax.vmap(jax.vmap(_project_simplex_masked))
    masks_b = jnp.broadcast_to(masks, W_mov.shape)
    upd_b = (upd > 0)[None, :, None]

    def loss_one(smu_r, svar_r):
        mk_mu, mk_var = compose_structure(structure, smu_r, svar_r)
        return mk_mu + lam_var * mk_var

    val_grad = jax.vmap(jax.value_and_grad(loss_one, argnums=(0, 1)))

    def cond(c):
        i, W, Wb, row_best, pool_best, stall = c
        return (i < steps) & (stall < patience)

    def body(c):
        i, W, Wb, row_best, pool_best, stall = c
        smu, svar, dmu, dvar = _stage_moments_grads(
            W, dist_ids, idxs, stats, num_t, impl, bfs)
        if fixed is not None:
            smu, svar = full(smu, f_mu), full(svar, f_var)
        if composed:
            losses, (g_mu, g_var) = val_grad(smu, svar)    # (R,), (R, S)
            G = take(g_mu)[..., None] * dmu + take(g_var)[..., None] * dvar
        else:
            losses = jnp.sum(smu, axis=1)
            G = dmu                                        # stage-local mean
        if sanitize:
            _san.check_finite(smu, "DAG stage means")
            _san.check_finite(G, "DAG PGD gradient")
        better = losses < row_best
        Wb = jnp.where(better[:, None, None], W, Wb)
        row_best = jnp.minimum(row_best, losses)
        cur = jnp.min(losses)
        moved = pool_best - cur > plateau_tol * jnp.abs(pool_best)
        stall = jnp.where(moved | (i < warmup), 0, stall + 1)
        pool_best = jnp.minimum(pool_best, cur)
        G = G / (jnp.linalg.norm(G, axis=-1, keepdims=True) + 1e-12)
        step = lr * 0.5 * (1.0 + jnp.cos(jnp.pi * i / steps))
        W = jnp.where(upd_b, proj(W - step * G, masks_b), W)
        if sanitize:
            _san.check_weight_rows(W, "DAG PGD iterate")
        return (i + 1, W, Wb, row_best, pool_best, stall)

    # 1e30, not inf: inf-inf poisons the first plateau comparison
    init = (jnp.int32(0), W_mov, W_mov, jnp.full((R,), 1e30, jnp.float32),
            jnp.float32(1e30), jnp.int32(0))
    i, W, Wb, row_best, _, _ = jax.lax.while_loop(cond, body, init)
    if fixed is not None:
        W, Wb = W0.at[:, mov, :].set(W), W0.at[:, mov, :].set(Wb)
    return W, Wb, row_best, i


@partial(jax.jit, static_argnames=("structure", "dist_ids", "idxs", "num_t",
                                   "impl", "bfs"))
def _score_dag(structure, dist_ids, idxs, stats, W, num_t: int, impl: str,
               bfs):
    """Composed (makespan mu, var) and stage moments for finalists W."""
    R, S, kmax = W.shape
    smu = jnp.zeros((R, S))
    svar = jnp.zeros((R, S))
    for g, dist_id in enumerate(dist_ids):
        idx = jnp.asarray(idxs[g])
        mus_g, sgs_g, ex_g = stats[g]
        n = mus_g.shape[0]
        rows = W[:, idx, :].reshape(R * n, kmax)
        m, v = ops.frontier_moments(
            rows, jnp.tile(mus_g, (R, 1)), jnp.tile(sgs_g, (R, 1)),
            num_t=num_t, impl=impl, block_f=bfs[g],
            family=(dist_id, jnp.tile(ex_g, (1, R, 1))))
        smu = smu.at[:, idx].set(m.reshape(R, n))
        svar = svar.at[:, idx].set(v.reshape(R, n))
    mk = jax.vmap(lambda m, v: jnp.stack(
        compose_structure(structure, m, v)))(smu, svar)
    return mk[:, 0], mk[:, 1], smu, svar


def _se_stacks(dag: StageDAG, groups, posteriors, kmax: int):
    """Per-group (se_mu, se_sigma) stacks, zero-padded like the stats."""
    ses = {}
    for name, nig in posteriors.items():
        se_mu, se_sg = nig_estimate_ses(nig)
        ses[name] = (np.asarray(se_mu, np.float64),
                     np.asarray(se_sg, np.float64))
    out = []
    for g in groups:
        n = len(g.idx)
        se_m = np.zeros((n, kmax))
        se_s = np.zeros((n, kmax))
        for j, i in enumerate(g.idx):
            s = dag.stages[i]
            if s.name in ses:
                se_m[j, :s.k], se_s[j, :s.k] = ses[s.name]
        out.append((se_m, se_s))
    return out


def _dag_fragility(structure, groups, stats, se_stacks, W, smu, svar,
                   num_t, impl, bfs):
    """Delta-method sd of the predicted makespan mean under estimation error.

    ``estimation_fragility`` chained through the composition: the stacked
    full-parameter launch gives every stage's d(mu_s, var_s)/d(mus, sigmas);
    the composition's cotangents d(mk_mu)/d(mu_s, var_s) come from autodiff
    over the Clark folds, taken at the smu/svar the candidates were SCORED
    at (the finalist evaluation is reused — only the parameter adjoints
    need a fresh launch, at the solve fidelity). Stage posteriors are
    independent, so the variance contributions add across stages AND
    channels.
    """
    R, S, kmax = W.shape
    gmk = jax.vmap(jax.grad(
        lambda m, v: compose_structure(structure, m, v)[0],
        argnums=(0, 1)))(smu, svar)
    with obs.span(obs_names.SPAN_SOLVER_WAIT, phase="fragility"):
        g_mu, g_var = (np.asarray(g, np.float64) for g in gmk)   # (R, S)
    frag2 = np.zeros(R)
    for g, grp in enumerate(groups):
        idx = np.asarray(grp.idx)
        n = len(grp.idx)
        mus_g, sgs_g, ex_g = stats[g]
        with obs.span(obs_names.SPAN_SOLVER_WAIT, phase="fragility"):
            rows = np.asarray(W[:, idx, :]).reshape(R * n, kmax)
        outs = ops.frontier_moments_with_grads(
            rows, np.tile(np.asarray(mus_g), (R, 1)),
            np.tile(np.asarray(sgs_g), (R, 1)),
            num_t=num_t, impl=impl, block_f=bfs[g],
            family=(grp.dist_id, jnp.tile(jnp.asarray(ex_g), (1, R, 1))),
            param_grads=True)
        with obs.span(obs_names.SPAN_SOLVER_WAIT, phase="fragility"):
            dmu_m, dvar_m = (
                np.asarray(outs[4], np.float64).reshape(R, n, kmax),
                np.asarray(outs[5], np.float64).reshape(R, n, kmax))
            dmu_s, dvar_s = (
                np.asarray(outs[6], np.float64).reshape(R, n, kmax),
                np.asarray(outs[7], np.float64).reshape(R, n, kmax))
        se_m, se_s = se_stacks[g]
        cm = g_mu[:, idx, None] * dmu_m + g_var[:, idx, None] * dvar_m
        cs = g_mu[:, idx, None] * dmu_s + g_var[:, idx, None] * dvar_s
        frag2 += ((cm * se_m) ** 2).sum(axis=(1, 2)) \
            + ((cs * se_s) ** 2).sum(axis=(1, 2))
    return np.sqrt(frag2)


# --------------------------------------------------------------------- solve
def _dag_with_done(dag: StageDAG, done: Dict[str, np.ndarray]) -> StageDAG:
    """Rescale named stages' statistics to their remaining work.

    Per-stage :func:`core.distributions.remaining_work_stats`: a half-done
    stage re-solves a fresh unit simplex over ``r``-scaled statistics; a
    fully-done stage degenerates to all-zero stats (every channel a point
    mass at 0 — zero duration, gates nothing).
    """
    mus_by, sgs_by, fam_by = {}, {}, {}
    from ..core.distributions import family_from_extra, remaining_work_stats
    for s in dag.stages:
        if s.name not in done:
            continue
        dist_id, extra = resolve_family(s.family, s.k)
        mus_r, sgs_r, extra_r, _ = remaining_work_stats(
            dist_id, np.asarray(s.mus), np.asarray(s.sigmas),
            np.asarray(extra), np.asarray(done[s.name]))
        # Stage validation requires strictly positive means; a fully-done
        # stage floors to a negligible point mass instead of zero
        mus_by[s.name] = np.maximum(mus_r, 1e-9)
        sgs_by[s.name] = sgs_r
        # Stage validates family specs through get_family, which rejects
        # lowered tuples — raise the rescaled extras back to an instance
        fam_by[s.name] = family_from_extra(dist_id, extra_r)
    return dag.with_stats(mus_by, sgs_by, fam_by)


def _starts(dag: StageDAG, mask: np.ndarray, kmax: int, restarts: int,
            warm_start, key, upd: Optional[np.ndarray] = None) -> np.ndarray:
    """(R, S, Kmax) start stack: equal, inverse-mu, warm, Dirichlet.

    ``upd`` (S,) 0/1 marks the dirty stages of an incremental re-solve.
    When given, the warm row is taken VERBATIM (no renormalization — it
    must already be a valid simplex row, e.g. any previous solve's output)
    and every start's FROZEN rows are overwritten with the warm rows, so
    all candidates agree bitwise on the stages the solve must not move.
    """
    S = len(dag.stages)
    act = mask.astype(np.float64)
    eq = act / act.sum(axis=1, keepdims=True)
    inv = np.zeros_like(eq)
    for i, s in enumerate(dag.stages):
        # floor guards the fully-done (all-zero-stats) re-solve stages
        w = 1.0 / np.maximum(np.asarray(s.mus), 1e-12)
        inv[i, :s.k] = w / w.sum()
    starts = [eq, inv]
    if warm_start is not None:
        wm = np.zeros((S, kmax))
        for i, s in enumerate(dag.stages):
            w = np.asarray(warm_start[s.name], np.float64)
            if upd is None:
                w = np.maximum(w, 0.0)
                wm[i, :s.k] = w / max(w.sum(), 1e-12)
            else:
                wm[i, :s.k] = w
        starts.insert(0, wm)
    if restarts > 0:
        rng = np.random.default_rng(
            0 if key is None else int(np.asarray(
                jax.random.key_data(key)).ravel()[-1]))
        for _ in range(restarts):
            e = rng.exponential(size=(S, kmax)) * act
            starts.append(e / np.maximum(e.sum(axis=1, keepdims=True),
                                         1e-12))
    out = np.stack(starts)
    if upd is not None:
        frozen = upd <= 0
        out[:, frozen, :] = out[0, frozen, :]
    return out.astype(np.float32)


def _launches(phase: str, mode: str, groups, ks, n: int, launches: int,
              kmax: int, num_t: int, bfs, impl: str) -> List[dict]:
    """``profile["launches"]`` entries of one rung, one per family group.

    The rung ran ``launches`` kernel launches per group, each over the
    group's stages for ``n`` candidates: ``rows`` real rows, padded to a
    multiple of the launch's block (``rows_padded``, as ``ops`` pads them)
    and every row to ``k`` channel slots, of which ``channels`` are real
    (the stages' own widths, ``ks`` indexed by ``group.idx``), at ``num_t``
    grid points. A PGD phase books its single-channel stages' one launch
    (``k`` 1, ``launches`` 1) before its loop's launches. ``pack`` is the
    lane slots a row (``autotune.pack_factor``; 1 on the XLA path) and
    ``lanes`` the lanes the launch's programs fill: each program's
    ``block_f * pack`` rounded up to 128.
    """
    out = []
    for g, bf in zip(groups, bfs):
        rows = n * len(g.idx)
        bf = max(min(bf, rows), 1)
        padded = -(-rows // bf) * bf
        pack = 1 if impl == "xla" else autotune.pack_factor(bf, kmax)
        out.append({"phase": phase, "mode": mode, "family": g.dist_id,
                    "launches": int(launches), "rows": rows,
                    "rows_padded": padded, "block_f": bf,
                    "k": kmax, "num_t": num_t,
                    "channels": n * sum(ks[i] for i in g.idx),
                    "pack": pack,
                    "lanes": padded // bf * autotune.LANES
                    * -(-bf * pack // autotune.LANES)})
    return out


class _PhaseClock:
    """Sequential phase attribution on the span API (PR 10).

    ``lap(next)`` closes the open ``solver.phase`` span, books its duration
    into ``phase_us``, and opens the next phase — so the ladder profile the
    benchmarks report and the spans a trace viewer shows are the SAME
    measurement, not two hand timers drifting apart. ``timed_span`` always
    measures; it records into the trace ring buffer only under
    ``REPRO_TRACE=1``.

    ``wait()`` spans a block on the device inside the open phase
    (``solver.wait``, recorded only when tracing is on). ``read(arrays)``
    is such a wait around ONE batched ``jax.device_get`` of a pytree;
    ``readbacks`` counts them (``profile["readbacks"]``).
    """

    def __init__(self, phase_us: Dict[str, float]):
        self.phase_us = phase_us
        self._open = None
        self.readbacks = 0

    def start(self, phase: str) -> None:
        self._open = obs.timed_span(obs_names.SPAN_SOLVER_PHASE,
                                    phase=phase).__enter__()

    def wait(self):
        return obs.span(obs_names.SPAN_SOLVER_WAIT,
                        phase=self._open.attrs["phase"])

    def read(self, arrays):
        self.readbacks += 1
        with self.wait():
            return jax.device_get(arrays)

    def lap(self, next_phase: Optional[str] = None) -> None:
        sp = self._open
        sp.__exit__(None, None, None)
        self.phase_us[sp.attrs["phase"]] = round(sp.dur_us, 1)
        self._open = None
        if next_phase is not None:
            self.start(next_phase)


def solve_dag(dag: StageDAG, lam_var: float = 0.0, steps: int = 120,
              restarts: int = 2, num_t: int = 1024, impl: str = "xla",
              block_f: Optional[int] = None,
              key: Optional[jax.Array] = None,
              warm_start: Optional[Dict[str, np.ndarray]] = None,
              risk_lam: float = 0.0,
              posteriors: Optional[Dict[str, object]] = None,
              presolve_steps: Optional[int] = None,
              eval_num_t: Optional[int] = None,
              done: Optional[Dict[str, np.ndarray]] = None,
              presolve_num_t: Optional[int] = None,
              prune_margin: Optional[float] = 5e-3,
              plateau_tol: float = 1e-6,
              plateau_patience: Optional[int] = 8,
              dirty: Optional[object] = None) -> DAGDecision:
    """Jointly optimize every stage's split for the end-to-end makespan.

    Objective: ``makespan_mu + lam_var * makespan_var`` composed through the
    DAG (series sums, Clark joins), descended by masked projected gradient
    over the concatenated stage simplices through a multi-fidelity ladder:

    1. stage-local presolve at ``presolve_num_t`` quadrature points
       (default min(num_t, 128)) — every stage to its own frontier;
    2. coarse triage: {starts, presolve snapshots} scored on the COMPOSED
       objective at ``presolve_num_t``; starts whose best coarse score
       trails the incumbent by more than ``prune_margin`` (relative) are
       dropped before any fine-fidelity work, and near-duplicate survivors
       collapse to one representative (``prune_margin=None`` disables the
       margin prune; the incumbent and the warm start always survive);
    3. composed refine of the survivors at ``num_t`` — warm from the
       presolve, so it descends with a small step — under plateau
       early-stop (``plateau_tol`` relative improvement, ``plateau_patience``
       consecutive stalls counted after a schedule warmup;
       ``plateau_patience=None`` restores the fixed step count);
    4. final pick: the surviving pool (refine inits, best-seen iterates,
       refined iterates) scored at ``eval_num_t`` (default
       max(num_t, 2048)) — coarse scores are triage-only, the returned
       split is ALWAYS chosen at evaluation fidelity, so the refine can
       only improve on the presolve and a warm start is never lost to an
       overshooting step.

    Every moment/gradient evaluation runs through ONE stacked
    ``ops.frontier_moments*`` launch per completion-time family present in
    the DAG — stages are rows, never a Python loop over kernel launches.
    A single-channel stage (split [1] whatever the iterate) is no row of
    the PGD phases: each phase computes its moments once, in a launch one
    channel wide, and composes them as constants; triage, the final score
    and the fragility score every stage, as the returned split holds them.
    Each (fidelity, mode) pair resolves its own autotuned block shape:
    ``num_t`` is part of the autotune key schema, so coarse-rung entries
    never cross-contaminate fine-rung silicon sweeps.

    ``warm_start``: per-stage weights of a previous solve (the balancer's
    refresh ticks). ``dirty`` (requires ``warm_start``) is the incremental
    re-solve contract: only the named stages' rows take PGD steps; frozen
    stages contribute moments to the composed makespan but their rows pass
    through bitwise (exact pass-throughs — bit-identical for
    float32-representable warm rows, which any previous solve's output
    is). An EMPTY dirty set returns the warm split verbatim (bitwise, no
    PGD launch) with moments from a single forward evaluation.

    ``risk_lam > 0`` with per-stage ``posteriors`` ({stage name: NIGState})
    scores finalists risk-adjusted by the composed estimation fragility;
    the fragility of the winning candidate is reported on the decision
    whenever posteriors are given (the balancer's adaptive refresh sizes
    its cadence by it) — with ``risk_lam == 0`` only the winner's
    fragility is computed (one single-row launch), reusing the finalist
    evaluation's moments for the composition cotangents.

    ``done`` ({stage name: per-channel completed work fractions}) is the
    sunk-work mid-flight re-solve: each named stage's statistics are rescaled
    to its remaining work through ``distributions.remaining_work_stats``
    before grouping, and its returned weights are shares of THAT REMAINING
    work (stages not named are solved for their full unit of work). A stage
    whose work is entirely done keeps zero weights and zero duration moments
    — it no longer gates its joins.

    ``decision.profile`` carries per-phase wall times (``phase_us``) and
    solver counters (starts, survivors, pool size, steps run per phase) so
    fidelity-ladder wins stay attributable, ``launches``: the kernel
    work of each rung and family group (:func:`_launches`), counted on the
    host from shapes, and ``readbacks``: the batched device-to-host
    transfers the ladder made (triage's and the final score's; the winner
    is indexed on the host from the final score's copy of the pool), and
    ``fixed_stages``: the single-channel stages composed as constants.
    """
    phase_us: Dict[str, float] = {}
    clock = _PhaseClock(phase_us)
    clock.start("starts")
    if done:
        dag = _dag_with_done(dag, done)
    S = len(dag.stages)
    pnt = min(presolve_num_t if presolve_num_t is not None
              else _COARSE_NUM_T, num_t)
    et = eval_num_t or max(num_t, 2048)

    upd_np = None
    if dirty is not None:
        dset = {str(n) for n in dirty}
        unknown = dset - {s.name for s in dag.stages}
        if unknown:
            raise KeyError(f"dirty stages not in the DAG: {sorted(unknown)}")
        if warm_start is None:
            raise ValueError("dirty= is an incremental re-solve and "
                             "requires warm_start")
        if not dset:
            # nothing moved: the warm split stands verbatim — one forward
            # evaluation for the reported moments, no PGD launch at all
            with obs.timed_span(obs_names.SPAN_SOLVER_PHASE,
                                phase="final_score") as sp:
                base = evaluate_dag(dag, warm_start, num_t=et, impl=impl)
            return DAGDecision(
                weights={s.name: np.asarray(warm_start[s.name],
                                            np.float64).copy()
                         for s in dag.stages},
                makespan_mu=base.makespan_mu,
                makespan_var=base.makespan_var,
                stage_mu=base.stage_mu, stage_var=base.stage_var,
                method="pgd-dag-noop", family_groups=base.family_groups,
                profile={"phase_us": {"final_score": round(sp.dur_us, 1)},
                         "noop": True, "starts": 0, "survivors": 0,
                         "pool": 1, "presolve_num_t": pnt,
                         "eval_num_t": et,
                         "launches": base.profile["launches"]})
        upd_np = np.array([1.0 if s.name in dset else 0.0
                           for s in dag.stages], np.float32)

    groups, mask, kmax = _stage_groups(dag)
    dist_ids, idxs, stats = _launch_args(groups)
    # a single-channel stage's split is [1] at every iterate: the PGD rows
    # are the stages whose split can move (``mov``), and the others
    # (``fix``) are composed as constants at their own width of one channel
    mov = tuple(i for i, s in enumerate(dag.stages) if s.k > 1)
    fix = tuple(i for i, s in enumerate(dag.stages) if s.k == 1)
    if fix:
        stage_rows = [(s.mus, s.sigmas, s.family) for s in dag.stages]
        groups_m, mask_m, _ = stack_rows([stage_rows[i] for i in mov], kmax)
        groups_f = stack_rows([stage_rows[i] for i in fix], 1)[0]
        ids_m, idxs_m, stats_m = _launch_args(groups_m)
    else:
        groups_m, mask_m, groups_f = groups, mask, []
        ids_m, idxs_m, stats_m = dist_ids, idxs, stats
    ids_f, idxs_f, stats_f = _launch_args(groups_f)
    W0h = _starts(dag, mask, kmax, restarts, warm_start, key, upd=upd_np)
    W0 = jnp.asarray(W0h)
    R = int(W0h.shape[0])
    upd = jnp.asarray(upd_np if upd_np is not None
                      else np.ones(S, np.float32))
    pre = presolve_steps if presolve_steps is not None else steps
    patience = (plateau_patience if plateau_patience is not None
                else max(steps, pre, 1))

    # every launch mode AND fidelity rung resolves its OWN block shape: the
    # fused pgrad working set is ~4x the grad one, the eval pass runs a
    # larger grid, and T is part of the autotune key so the coarse rung's
    # swept entries never shadow the fine rung's
    def _bf(g, rows, nt, fused, params, k=kmax):
        if block_f is not None:
            return max(min(block_f, rows), 1)
        return autotune.lookup(rows, k, nt, backend=impl, fused=fused,
                               dist_id=g.dist_id, params=params,
                               stacked=True)

    def _run_phase(W_in, n, composed, n_steps, nt, lr, warmup):
        """A PGD phase over ``n`` candidates: ``(outputs, bfs, fixed bfs)``.
        With no movable stage it returns its start, in no step."""
        bfs_m = tuple(_bf(g, n * len(g.idx), nt, True, False)
                      for g in groups_m)
        bfs_f = tuple(_bf(g, n * len(g.idx), nt, True, False, 1)
                      for g in groups_f)
        if not mov:
            return (W_in, W_in, None, 0), bfs_m, bfs_f
        kw = dict(steps=n_steps, patience=patience, num_t=nt, impl=impl,
                  bfs=bfs_m, composed=composed, lr=lr, warmup=warmup,
                  fixed=(mov, fix, ids_f, idxs_f, bfs_f) if fix else None)
        args = (dag.structure, ids_m, idxs_m, stats_m, jnp.asarray(mask_m),
                W_in, upd, jnp.float32(lam_var), jnp.float32(plateau_tol))
        if _san.enabled():
            out = _san.run_checked(partial(_pgd_phase, sanitize=True, **kw),
                                   *args, fixed_stats=stats_f)
        else:
            out = _pgd_phase(*args, fixed_stats=stats_f, **kw)
        return out, bfs_m, bfs_f

    if _san.enabled():
        # sanitizer tier: eager boundary validation of the stage statistics
        # once, then both jitted phases under checkify (analysis.sanitize)
        _san.assert_weight_rows(W0h)
        for g in groups:
            _san.assert_finite("stage mus", g.mus)
            _san.assert_finite("stage sigmas", g.sigmas)
            _san.assert_nonneg("stage sigmas", g.sigmas)

    clock.lap("presolve")

    # --- phase 1: stage-local presolve at the coarse rung; stall counting
    # waits out the first half of the cosine schedule (cold starts spend it
    # in large-step transit where the pool best moves in bursts)
    (W1, _, _, n_pre), bfs_pre, bfs_pre_f = _run_phase(
        W0, R, False, pre, pnt, _PRESOLVE_LR, pre // 2)
    with clock.wait():
        jax.block_until_ready(W1)
    clock.lap("triage")

    # --- coarse triage: composed scores of {starts, presolve} at the same
    # rung; the coarse/fine quadrature bias is shared across candidates, so
    # the RANKING is meaningful at far lower resolution than the moments
    pool0 = jnp.concatenate([W0, W1], axis=0)
    bfs_tri = tuple(_bf(g, 2 * R * len(g.idx), pnt, False, False)
                    for g in groups)
    c_mu, c_var, _, _ = _score_dag(dag.structure, dist_ids, idxs, stats,
                                   pool0, pnt, impl, bfs_tri)
    c_mu, c_var, W1h = clock.read((c_mu, c_var, W1))
    csc = np.asarray(c_mu, np.float64) + lam_var * np.asarray(
        c_var, np.float64)
    per_start = np.minimum(csc[:R], csc[R:])
    Wch = np.where((csc[R:] <= csc[:R])[:, None, None], W1h, W0h)
    if prune_margin is None:
        keep = np.ones(R, bool)
    else:
        inc = float(per_start.min())
        keep = per_start <= inc + prune_margin * max(abs(inc), 1e-12)
        keep[int(np.argmin(per_start))] = True
    # collapse near-duplicate survivors: independent starts routinely
    # presolve to the SAME frontier point; only the best-scored
    # representative of each cluster goes on to fine-fidelity refinement
    chosen: List[int] = []
    for i in np.argsort(per_start, kind="stable"):
        if not keep[i]:
            continue
        if any(float(np.abs(Wch[i] - Wch[j]).max()) <= _DEDUPE_TOL
               for j in chosen):
            keep[i] = False
        else:
            chosen.append(int(i))
    if warm_start is not None:
        keep[0] = True   # the warm start is never lost to coarse triage
    survivors = int(keep.sum())
    Wr0 = jnp.asarray(Wch[np.flatnonzero(keep)])
    clock.lap("refine")

    # --- phase 2: composed refine of the survivors at solve fidelity; the
    # survivors are presolved (near-frontier) so the step is small, but the
    # fixed-size normalized-gradient steps still orbit the optimum until the
    # cosine decay shrinks them — stalls count from mid-schedule here too
    (Wf, Wb, _, n_ref), bfs_ref, bfs_ref_f = _run_phase(
        Wr0, survivors, True, steps, num_t, _REFINE_LR, steps // 2)
    with clock.wait():
        jax.block_until_ready(Wf)
    clock.lap("final_score")

    # --- final pick at evaluation fidelity: refine inits (which include the
    # triage winners and any warm start), best-seen and final iterates
    cands = jnp.concatenate([Wr0, Wb, Wf], axis=0)
    ncand = int(cands.shape[0])
    bfs_eval = tuple(_bf(g, ncand * len(g.idx), et, False, False)
                     for g in groups)
    mk_mu, mk_var, smu, svar = _score_dag(dag.structure, dist_ids, idxs,
                                          stats, cands, et, impl, bfs_eval)
    # the whole pool comes back in the final score's one transfer; the
    # winner is then indexed on the host, from the same float32 values
    mk_mu_h, mk_var_h, smu_h, svar_h, cands_h, n_pre, n_ref = clock.read(
        (mk_mu, mk_var, smu, svar, cands, n_pre, n_ref))
    score = np.asarray(mk_mu_h, np.float64) + lam_var * np.asarray(
        mk_var_h, np.float64)
    if posteriors is not None:
        clock.lap("fragility")

    method = ("pgd-dag-joint-inc" if upd_np is not None else "pgd-dag-joint")
    frag = None
    se_stacks = None
    if posteriors is not None:
        se_stacks = _se_stacks(dag, groups, posteriors, kmax)
        if risk_lam > 0.0:
            bfs_frag = tuple(_bf(g, ncand * len(g.idx), num_t, True, True)
                             for g in groups)
            frag = _dag_fragility(dag.structure, groups, stats, se_stacks,
                                  cands, smu, svar, num_t, impl, bfs_frag)
            score = score + risk_lam * frag
            method += "-risk"
    best = int(np.argmin(score))
    frag_best = None
    if frag is not None:
        frag_best = float(frag[best])
    elif posteriors is not None:
        # reported fragility only: one single-row pgrad launch for the
        # WINNER, reusing its eval-fidelity moments for the composition
        # cotangents instead of re-launching the whole candidate pool
        bfs_frag = tuple(_bf(g, len(g.idx), num_t, True, True)
                         for g in groups)
        fb = _dag_fragility(dag.structure, groups, stats, se_stacks,
                            cands[best:best + 1], smu[best:best + 1],
                            svar[best:best + 1], num_t, impl, bfs_frag)
        frag_best = float(fb[0])
    Wbest = np.asarray(cands_h[best], np.float64)
    mk_best, var_best = float(mk_mu_h[best]), float(mk_var_h[best])
    smu_best = np.asarray(smu_h[best], np.float64)
    svar_best = np.asarray(svar_h[best], np.float64)
    n_pre, n_ref = int(n_pre), int(n_ref)
    clock.lap()

    weights = {s.name: Wbest[i, :s.k] for i, s in enumerate(dag.stages)}
    ks = [s.k for s in dag.stages]

    def pgd_launches(phase, n, steps_run, nt, bfs_m, bfs_f):
        # the fixed rows' one launch of the phase, then the loop's
        if not mov:
            return []
        return (_launches(phase, "grad", groups_f, [1] * len(fix), n, 1, 1,
                          nt, bfs_f, impl)
                + _launches(phase, "grad", groups_m, [ks[i] for i in mov], n,
                            steps_run, kmax, nt, bfs_m, impl))

    launches = (
        pgd_launches("presolve", R, n_pre, pnt, bfs_pre, bfs_pre_f)
        + _launches("triage", "fwd", groups, ks, 2 * R, 1, kmax, pnt, bfs_tri,
                    impl)
        + pgd_launches("refine", survivors, n_ref, num_t, bfs_ref, bfs_ref_f)
        + _launches("final_score", "fwd", groups, ks, ncand, 1, kmax, et,
                    bfs_eval, impl))
    if posteriors is not None:
        launches += _launches("fragility", "pgrad", groups, ks,
                              ncand if frag is not None else 1, 1, kmax,
                              num_t, bfs_frag, impl)
    profile = {"phase_us": phase_us, "starts": R, "survivors": survivors,
               "pool": ncand, "presolve_num_t": pnt, "eval_num_t": et,
               "presolve_steps_run": n_pre, "refine_steps_run": n_ref,
               "launches": launches, "readbacks": clock.readbacks,
               "fixed_stages": len(fix)}
    return DAGDecision(
        weights=weights, makespan_mu=mk_best, makespan_var=var_best,
        stage_mu=smu_best, stage_var=svar_best,
        method=method, family_groups=len(groups),
        fragility=frag_best, profile=profile)


def evaluate_dag(dag: StageDAG, weights: Dict[str, np.ndarray],
                 num_t: int = 2048, impl: str = "xla") -> DAGDecision:
    """Composed moments of an arbitrary per-stage split (shared evaluator:
    joint and greedy decisions are compared on the SAME quadrature)."""
    groups, mask, kmax = _stage_groups(dag)
    dist_ids, idxs, stats = _launch_args(groups)
    S = len(dag.stages)
    W = np.zeros((1, S, kmax), np.float32)
    for i, s in enumerate(dag.stages):
        w = np.maximum(np.asarray(weights[s.name], np.float64), 0.0)
        W[0, i, :s.k] = w / max(w.sum(), 1e-12)
    bfs = tuple(autotune.lookup(len(g.idx), kmax, num_t, backend=impl,
                                fused=False, dist_id=g.dist_id, stacked=True)
                for g in groups)
    mk_mu, mk_var, smu, svar = _score_dag(dag.structure, dist_ids, idxs,
                                          stats, jnp.asarray(W), num_t,
                                          impl, bfs)
    return DAGDecision(
        weights={s.name: np.asarray(W[0, i, :s.k], np.float64)
                 for i, s in enumerate(dag.stages)},
        makespan_mu=float(mk_mu[0]), makespan_var=float(mk_var[0]),
        stage_mu=np.asarray(smu[0], np.float64),
        stage_var=np.asarray(svar[0], np.float64),
        method="evaluate", family_groups=len(groups),
        profile={"launches": _launches("final_score", "fwd", groups,
                                       [s.k for s in dag.stages], 1, 1,
                                       kmax, num_t, bfs, impl)})


def solve_dag_greedy(dag: StageDAG, lam: float = 0.0, steps: int = 120,
                     restarts: int = 2, num_t: int = 1024,
                     impl: str = "xla",
                     eval_num_t: Optional[int] = None,
                     presolve_num_t: Optional[int] = None,
                     warm_start: Optional[Dict[str, np.ndarray]] = None,
                     dirty: Optional[object] = None) -> DAGDecision:
    """Stage-by-stage baseline: each stage solved alone (``mu + lam var`` on
    its OWN join time), blind to where it sits in the graph — a per-stage
    Python loop over independent solves, the thing the joint solver
    replaces. Composed moments evaluated with the shared evaluator.

    The joint solver's knobs ride along for like-for-like comparisons:
    ``presolve_num_t`` runs the per-stage solves at a coarse quadrature
    rung (default None keeps them at ``num_t`` — the tracked baseline);
    ``dirty`` (requires ``warm_start``) copies the warm split verbatim for
    stages outside the set and re-solves only the dirty ones, warm-started.
    """
    if dirty is not None:
        dset = {str(n) for n in dirty}
        unknown = dset - {s.name for s in dag.stages}
        if unknown:
            raise KeyError(f"dirty stages not in the DAG: {sorted(unknown)}")
        if warm_start is None:
            raise ValueError("dirty= is an incremental re-solve and "
                             "requires warm_start")
    else:
        dset = None
    solve_t = num_t if presolve_num_t is None else min(presolve_num_t, num_t)
    weights = {}
    with obs.timed_span(obs_names.SPAN_SOLVER_PHASE,
                        phase="stage_solves") as sp_solve:
        for s in dag.stages:
            if dset is not None and s.name not in dset:
                weights[s.name] = np.asarray(warm_start[s.name],
                                             np.float64).copy()
                continue
            dec = optimize_weights(
                s.mus, s.sigmas, lam=lam, steps=steps, restarts=restarts,
                num_t=solve_t, impl=impl, family=s.family,
                warm_start=(None if warm_start is None
                            else warm_start.get(s.name)),
                eval_num_t=num_t)
            weights[s.name] = dec.weights
    with obs.timed_span(obs_names.SPAN_SOLVER_PHASE,
                        phase="final_score") as sp_eval:
        out = evaluate_dag(dag, weights, num_t=eval_num_t or max(num_t, 2048),
                           impl=impl)
    profile = {"phase_us": {"stage_solves": round(sp_solve.dur_us, 1),
                            "final_score": round(sp_eval.dur_us, 1)},
               "solve_num_t": solve_t}
    return DAGDecision(
        weights=weights, makespan_mu=out.makespan_mu,
        makespan_var=out.makespan_var, stage_mu=out.stage_mu,
        stage_var=out.stage_var, method="greedy-per-stage",
        family_groups=out.family_groups, profile=profile)
