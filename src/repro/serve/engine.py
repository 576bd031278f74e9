"""Serving tier: the partitioned batcher (the paper's file-transfer
scenario mapped to request routing across replica groups) and the
continuous-batching :class:`WorkflowEngine`.

The engine is the serving-tier answer to the question the paper answers for
one workflow: a production system prices partition splits for MANY
concurrent workflows at once, the way an inference server batches decode
steps across requests. Every live workflow *instance* — its remaining
stages, its posterior-specific ``(mus, sigmas, extra)``, its sunk work —
becomes rows of ONE shared stacked ``ops.frontier_moments_with_grads``
launch per completion-time family per tick (``workflow.solve.stack_rows``
does the row-block bookkeeping), so solver cost is amortized across the
whole live set instead of paid per workflow. The per-instance Python loop
this replaces is now a lint error under ``serve/`` (RPA080).
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from time import perf_counter
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import autotune, ops
from ..obs import events as obs_events
from ..obs import names as obs_names
from ..obs import trace as obs
from ..sched.balancer import (InstanceHeads, UncertaintyAwareBalancer,
                              integerize)
from ..sim.cluster import ClusterSim, WorkflowSim
from ..workflow.solve import _project_simplex_masked, stack_rows
from .telemetry import ServeTelemetry

__all__ = ["ReplicaGroup", "PartitionedBatcher", "WorkflowEngine",
           "row_pgd_step"]


@dataclass
class ReplicaGroup:
    """A serving channel: a replica set with its own speed distribution.

    Its service times come from the batcher's simulated channels."""
    name: str


class PartitionedBatcher:
    """Split request batches across replica groups by the paper's frontier.

    The batch of R requests is the workflow D; replica groups are channels;
    the response is complete when the *slowest* group returns (the join).
    The balancer learns per-group (mu, sigma) per-request service rates online
    and re-partitions every batch.
    """

    def __init__(self, groups: List[ReplicaGroup], lam: float = 0.05,
                 policy: str = "frontier", sim: Optional[ClusterSim] = None,
                 seed: int = 0, impl: str = "xla", num_t: int = 1024,
                 refresh_every: int = 1, family="normal",
                 risk_lam: float = 0.0, adaptive_refresh: bool = False,
                 block_f=None):
        self.groups = groups
        # forward the solver knobs so serving ticks run the kernel-backed
        # (and, with impl="pallas", compiled) fused solve path online;
        # ``family`` swaps the completion-time model the frontier solves
        # under (e.g. "lognormal" for heavy-tailed WAN-style service times,
        # or "auto" to let the balancer BIC-select the model from the
        # observed rate history and switch it with hysteresis)
        self.balancer = UncertaintyAwareBalancer(len(groups), lam=lam,
                                                 policy=policy, impl=impl,
                                                 num_t=num_t,
                                                 refresh_every=refresh_every,
                                                 family=family,
                                                 risk_lam=risk_lam,
                                                 adaptive_refresh=adaptive_refresh,
                                                 block_f=block_f)
        self.sim = sim or ClusterSim.heterogeneous(len(groups), seed=seed)
        self.last_tick: Optional[dict] = None

    def split(self, num_requests: int) -> np.ndarray:
        return integerize(self.balancer.weights(), num_requests)

    @property
    def selected_family(self) -> str:
        """dist_id of the family the balancer is currently solving under
        (moves over time when ``family="auto"``)."""
        return self.balancer.selected_family.dist_id

    def run_batch(self, requests: np.ndarray) -> Tuple[float, np.ndarray]:
        """Route one batch (one entry of ``requests`` per request). Returns
        (join_latency, counts).

        Latency comes from the simulator channels (the timing physics live
        in sim, as the paper's did in background-process contention).
        Per-tick telemetry — including the family the solve ran under,
        which is the interesting signal in ``family="auto"`` mode — lands
        in ``self.last_tick``.
        """
        R = len(requests)
        counts = self.split(R)
        fam = self.selected_family
        join_t, durs = self.sim.run_step(counts.astype(np.float64) / max(R, 1))
        self.balancer.observe(durs, counts.astype(np.float64) / max(R, 1))
        self.last_tick = {
            "family": fam,
            "join_latency": float(join_t),
            "counts": counts,
            "effective_refresh": self.balancer.effective_refresh,
        }
        return join_t, counts

    # ------------------------------------------------------------ persistence
    def state_dict(self) -> dict:
        """Balancer AND sim-world snapshot: a batcher restored from this
        replays bitwise-identical ticks (same splits, same simulated
        durations, same posterior updates) — see ckpt/store.py's
        kill/restore tick-parity contract. Replica groups are code-side
        configuration, like the workflow balancer's DAG."""
        return {"balancer": self.balancer.state_dict(),
                "sim": self.sim.state_dict()}

    def load_state_dict(self, d: dict):
        self.balancer = UncertaintyAwareBalancer.from_state_dict(
            d["balancer"])
        self.sim = ClusterSim.from_state_dict(d["sim"])
        return self

    @classmethod
    def from_state_dict(cls, d: dict,
                        groups: List[ReplicaGroup]) -> "PartitionedBatcher":
        return cls(groups).load_state_dict(d)


# --------------------------------------------------------------------------
# continuous-batching workflow engine
# --------------------------------------------------------------------------

@jax.jit
def _row_step(W, dmu, dvar, lam, mask, lr):
    """One normalized-gradient PGD step on every row's masked simplex.

    Per-row objective is stage-local ``mu + lam_row * var`` (``lam_row``
    carries each instance's SLO urgency); the gradient is L2-normalized per
    row so one shared step size serves instances whose stages live at very
    different time scales — the same normalization the DAG solver uses.
    """
    G = dmu + lam[:, None] * dvar
    G = G / (jnp.linalg.norm(G, axis=-1, keepdims=True) + 1e-12)
    return jax.vmap(_project_simplex_masked)(W - lr * G, mask)


def row_pgd_step(W, mus, sigmas, dist_id, extra, lam, mask, *, num_t,
                 impl: str = "xla", lr: float = 0.02,
                 block_f: Optional[int] = None):
    """One fused moments+gradients launch + PGD step over a stacked row set.

    This is the batched tick's unit of work as a pure function: ``W`` /
    ``mus`` / ``sigmas`` are ``(F, K)`` stacked rows of ONE family
    (``dist_id`` static, ``extra`` the ``(E, F, K)`` per-row shape
    parameters), ``lam`` the per-row risk weight, ``mask`` the per-row
    active-channel mask. Returns ``(mu, var, W_next)`` as numpy — the
    moments are evaluated at the INCOMING ``W`` (they price the current
    split; the stepped ``W_next`` is priced next tick). Also the
    per-instance baseline unit in ``benchmarks/serve_trace.py`` — the
    benchmark's looped baseline calls this once per instance, the engine
    once per family group.
    """
    F, K = W.shape
    if block_f is None:
        block_f = autotune.lookup(F, K, num_t, backend=impl, fused=True,
                                  dist_id=dist_id, stacked=True)
    m, v, dm, dv = ops.frontier_moments_with_grads(
        jnp.asarray(W, jnp.float32), jnp.asarray(mus, jnp.float32),
        jnp.asarray(sigmas, jnp.float32), num_t=num_t, impl=impl,
        block_f=block_f, family=(dist_id, jnp.asarray(extra, jnp.float32)))
    W2 = _row_step(jnp.asarray(W, jnp.float32), dm, dv,
                   jnp.asarray(lam, jnp.float32),
                   jnp.asarray(mask, jnp.float32),
                   jnp.float32(lr))
    return np.asarray(m, np.float64), np.asarray(v, np.float64), \
        np.asarray(W2, np.float64)


@dataclass
class _EngineRow:
    """One (instance, remaining stage) pair of the current solve tick."""

    iid: int
    stage: str
    key: str                      # heads key: "template/stage"
    k: int
    mus: np.ndarray               # (k,) posterior point estimates
    sigmas: np.ndarray            # (k,)
    family: object                # the head's selected ChannelFamily
    lam: float                    # instance risk weight (SLO urgency)
    w: np.ndarray                 # (k,) incoming split (priced this launch)
    mu: Optional[float] = None    # set by the launch
    var: Optional[float] = None


@dataclass
class _Instance:
    """One live workflow instance: its progress, splits and solve state."""

    iid: int
    template: str
    deadline: float               # SLO bound on the makespan (sim seconds)
    admitted_tick: int
    elapsed: float = 0.0          # makespan so far (max stage completion)
    completions: dict = field(default_factory=dict)   # stage -> finish time
    weights: dict = field(default_factory=dict)       # stage -> (K_s,)
    stage_mu: dict = field(default_factory=dict)      # last priced moments
    stage_var: dict = field(default_factory=dict)
    steps_left: int = 0           # pending PGD descents (dirty when > 0)
    lam: float = 0.0              # risk weight at the last solve
    stat_snap: dict = field(default_factory=dict)     # stats at last solve


class WorkflowEngine:
    """Admission-queue continuous-batching engine over workflow instances.

    ``templates`` maps template name -> :class:`~repro.workflow.dag.StageDAG`
    (the workflow shapes this engine serves); each template gets one shared
    :class:`WorkflowSim` stage-fleet world (instances of a template contend
    for the same physical channels, tick by tick). A request enters via
    :meth:`submit` (template + optional SLO deadline), waits in the
    admission queue while the live set is full, and once admitted becomes a
    live instance with its own forked estimation heads
    (:class:`~repro.sched.balancer.InstanceHeads`).

    One :meth:`tick` runs the continuous-batching cycle:

    1. **admit** — pending requests fill free live slots.
    2. **solve** — every dirty instance's remaining stages become rows of
       one stacked fused launch per completion-time family
       (``stack_rows`` groups them; the row axis pads to an
       ``autotune.bucket_rows`` bucket so the jit/autotune caches stay
       warm across fluctuating live counts). Each row descends one
       normalized-PGD step on its stage simplex; moments from the SAME
       launch feed telemetry and SLO prediction — no second launch.
    3. **execute** — each instance runs its released wave (stages whose
       predecessors completed) on the template's sim fleet; observations
       feed the instance head AND the template prototype.
    4. **retire** — finished instances record join latency and SLO
       verdicts and free their slot.

    **Dirtiness (the engine-level ``dirty=`` contract).** An instance is
    dirty while ``steps_left > 0``: admission starts it at
    ``settle_steps``, and a settled instance re-dirties only when its
    posteriors drift past ``dirty_tol`` (relative, vs the stats its last
    solve priced) or its SLO urgency moves by more than ``dirty_tol``
    relative. Clean instances contribute NO rows — their splits stand
    verbatim, so solver cost tracks the drift rate, not the live count.

    **SLO -> risk.** Each instance's row weight is ``lam_var + slo_gain *
    min(predicted_remaining / slack, slo_lam_cap)``: an instance burning
    its deadline budget pays increasingly for variance, which is exactly
    the paper's mean-variance frontier driven by urgency.
    """

    def __init__(self, templates: Dict[str, object], *, max_live: int = 256,
                 lam_var: float = 0.0, slo_gain: float = 0.5,
                 slo_lam_cap: float = 4.0, settle_steps: int = 6,
                 dirty_tol: float = 0.05, lr: float = 0.02,
                 num_t: int = 256, impl: str = "xla", seed: int = 0,
                 prior_obs: int = 0, telemetry_capacity: int = 2048):
        if not templates:
            raise ValueError("WorkflowEngine needs at least one template")
        self.templates = dict(templates)
        self.max_live = int(max_live)
        self.lam_var = float(lam_var)
        self.slo_gain = float(slo_gain)
        self.slo_lam_cap = float(slo_lam_cap)
        self.settle_steps = int(settle_steps)
        self.dirty_tol = float(dirty_tol)
        self.lr = float(lr)
        self.num_t = int(num_t)
        self.impl = impl
        self.seed = int(seed)
        self.sims: Dict[str, WorkflowSim] = {
            name: WorkflowSim.from_dag(dag, seed=seed + 1000 * i)
            for i, (name, dag) in enumerate(self.templates.items())}
        prototypes = {}
        for name, dag in self.templates.items():
            for s in dag.stages:
                prototypes[f"{name}/{s.name}"] = UncertaintyAwareBalancer(
                    num_channels=s.k, family=s.family,
                    prior_mean=float(np.mean(s.mus)), explore=0.0)
                if prior_obs:
                    # optional warm prior: feed the template's declared
                    # stats as synthetic observations so first admissions
                    # price heterogeneous channels instead of a flat prior
                    w = np.full(s.k, 1.0 / s.k)
                    for _ in range(prior_obs):
                        prototypes[f"{name}/{s.name}"].observe(
                            s.mus * w, w)
        self.heads = InstanceHeads(prototypes)
        # the pinned channel axis: every stacked launch pads to this K so
        # the jit cache keys only by row bucket, never by the live mix
        self.kmax = max(s.k for dag in self.templates.values()
                        for s in dag.stages)
        self.telemetry = ServeTelemetry(capacity=telemetry_capacity,
                                        seed=seed)
        self._queue: deque = deque()
        self._live: Dict[int, _Instance] = {}
        self._next_iid = 0
        self.tick_count = 0
        self.last_tick: Optional[dict] = None
        self.last_rows: List[_EngineRow] = []

    # ------------------------------------------------------------ admission
    def submit(self, template: str, deadline: Optional[float] = None) -> int:
        """Enqueue one workflow request; returns its instance id.

        ``deadline`` is the SLO bound on the instance's end-to-end makespan
        in simulated seconds (None = no SLO: the instance solves at the
        engine's base ``lam_var``).
        """
        if template not in self.templates:
            raise ValueError(f"unknown template {template!r} "
                             f"(templates: {sorted(self.templates)})")
        iid = self._next_iid
        self._next_iid += 1
        self._queue.append({"iid": iid, "template": template,
                            "deadline": (float("inf") if deadline is None
                                         else float(deadline)),
                            "queued_tick": self.tick_count})
        return iid

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def live_count(self) -> int:
        return len(self._live)

    def set_load(self, factor: float, template: Optional[str] = None):
        """Regime switch on one template's sim world or all of them."""
        sims = ([self.sims[template]] if template is not None
                else self.sims.values())
        for sim in sims:
            sim.set_load(factor)

    def _admit(self) -> int:
        admitted = 0
        while self._queue and len(self._live) < self.max_live:
            req = self._queue.popleft()
            iid, tpl = req["iid"], req["template"]
            dag = self.templates[tpl]
            self.heads.admit(iid, [f"{tpl}/{s.name}" for s in dag.stages])
            inst = _Instance(iid=iid, template=tpl,
                             deadline=req["deadline"],
                             admitted_tick=self.tick_count,
                             steps_left=self.settle_steps)
            for s in dag.stages:
                inst.weights[s.name] = np.full(s.k, 1.0 / s.k)
            self._live[iid] = inst
            # dirty-set membership is auditable from birth: admission IS
            # the first dirty interval (steps_left = settle_steps)
            obs_events.dirty("engine", str(iid), "admit")
            self.telemetry.bump("admitted")
            self.telemetry.add("queue_wait_ticks",
                               self.tick_count - req["queued_tick"])
            admitted += 1
        return admitted

    # ------------------------------------------------------------ solve
    def _predicted_remaining(self, inst: _Instance) -> float:
        """Longest-path predicted time over the instance's remaining stages
        (host-side, O(S)): last-priced stage means where a solve has run,
        else the head's naive equal-split estimate."""
        dag = self.templates[inst.template]
        lp: Dict[str, float] = {}
        best = 0.0
        for name in dag.topo_order:
            if name in inst.completions:
                continue
            if name in inst.stage_mu:
                mu_s = inst.stage_mu[name]
            else:
                mus, _ = self.heads.estimates(inst.iid,
                                              f"{inst.template}/{name}")
                mu_s = float(np.mean(mus)) / max(len(mus), 1)
            rel = max((lp[u] for u in dag.predecessors(name) if u in lp),
                      default=0.0)
            lp[name] = rel + float(mu_s)
            best = max(best, lp[name])
        return best

    def _row_lam(self, inst: _Instance) -> float:
        if not np.isfinite(inst.deadline):
            return self.lam_var
        slack = max(inst.deadline - inst.elapsed, 1e-9)
        urgency = self._predicted_remaining(inst) / slack
        return self.lam_var + self.slo_gain * min(urgency, self.slo_lam_cap)

    def _maybe_redirty(self, inst: _Instance) -> None:
        """Posterior / urgency drift check for a settled instance."""
        tpl = inst.template
        for name in self.templates[tpl].names:
            if name in inst.completions or name not in inst.stat_snap:
                continue
            mus, sigmas = self.heads.estimates(inst.iid, f"{tpl}/{name}")
            mu0, sg0 = inst.stat_snap[name]
            drift = max(float(np.max(np.abs(mus - mu0) / np.abs(mu0))),
                        float(np.max(np.abs(sigmas - sg0)
                                     / np.maximum(np.abs(mu0), 1e-12))))
            if drift > self.dirty_tol:
                inst.steps_left = self.settle_steps
                obs_events.dirty("engine", f"{inst.iid}/{name}", "drift",
                                 drift)
                return
        lam_now = self._row_lam(inst)
        if abs(lam_now - inst.lam) > self.dirty_tol * max(abs(inst.lam),
                                                          1.0):
            inst.steps_left = self.settle_steps
            obs_events.dirty("engine", str(inst.iid), "slo",
                             abs(lam_now - inst.lam))

    def _gather_rows(self) -> List[_EngineRow]:
        rows: List[_EngineRow] = []
        for inst in self._live.values():
            if inst.steps_left <= 0:
                self._maybe_redirty(inst)
            if inst.steps_left <= 0:
                continue
            lam_i = self._row_lam(inst)
            if obs.enabled() and lam_i > self.lam_var:
                obs_events.slo_lam(inst.iid, lam_i, self.lam_var,
                                   headroom=inst.deadline - inst.elapsed)
            tpl = inst.template
            for s in self.templates[tpl].stages:
                if s.name in inst.completions:
                    continue  # sunk work: completed stages leave the solve
                key = f"{tpl}/{s.name}"
                mus, sigmas = self.heads.estimates(inst.iid, key)
                rows.append(_EngineRow(
                    iid=inst.iid, stage=s.name, key=key, k=s.k,
                    mus=np.asarray(mus, np.float64),
                    sigmas=np.asarray(sigmas, np.float64),
                    family=self.heads.family(inst.iid, key),
                    lam=lam_i, w=inst.weights[s.name]))
        return rows

    def _solve_tick(self, rows: List[_EngineRow]) -> int:
        """One batched solve: ONE fused launch per family group, padded to
        the row bucket; write stepped splits and priced moments back."""
        t0 = perf_counter()
        groups, mask, kmax = stack_rows(
            [(r.mus, r.sigmas, r.family) for r in rows], kmax=self.kmax)
        launches = 0
        for g in groups:
            n = len(g.idx)
            F = autotune.bucket_rows(n)
            E = g.extra.shape[0]
            W = np.zeros((F, kmax), np.float32)
            mus = np.zeros((F, kmax), np.float32)
            sgs = np.zeros((F, kmax), np.float32)
            ex = np.zeros((E, F, kmax), np.float32)
            msk = np.zeros((F, kmax), np.float32)
            lam = np.zeros(F, np.float32)
            for j, ridx in enumerate(g.idx):
                r = rows[ridx]
                W[j, :r.k] = r.w
                msk[j] = mask[ridx]
                lam[j] = r.lam
            mus[:n], sgs[:n], ex[:, :n] = g.mus, g.sigmas, g.extra
            if F > n:  # pad rows repeat row 0 (sliced off after the launch)
                W[n:], mus[n:], sgs[n:] = W[0], mus[0], sgs[0]
                ex[:, n:] = ex[:, :1]
                msk[n:], lam[n:] = msk[0], lam[0]
            with obs.span(obs_names.SPAN_SOLVER_PGD, family=g.dist_id,
                          rows=n, F=F, K=kmax, num_t=self.num_t):
                m, v, W2 = row_pgd_step(W, mus, sgs, g.dist_id, ex, lam,
                                        msk, num_t=self.num_t,
                                        impl=self.impl, lr=self.lr)
            launches += 1
            self.telemetry.bump("launches")
            self.telemetry.add("rows_per_launch", n)
            self.telemetry.add("row_occupancy", n / F)
            for j, ridx in enumerate(g.idx):
                r = rows[ridx]
                inst = self._live[r.iid]
                inst.weights[r.stage] = np.asarray(W2[j, :r.k], np.float64)
                inst.stage_mu[r.stage] = float(m[j])
                inst.stage_var[r.stage] = float(v[j])
                inst.stat_snap[r.stage] = (r.mus.copy(), r.sigmas.copy())
                r.mu, r.var = float(m[j]), float(v[j])
        # one descent consumed; the urgency each row solved under is the
        # baseline the next re-dirty check compares against
        for r in rows:
            self._live[r.iid].lam = r.lam
        for iid in {r.iid for r in rows}:
            self._live[iid].steps_left -= 1
        self.telemetry.add("solver_tick_us", (perf_counter() - t0) * 1e6)
        return launches

    # ------------------------------------------------------------ execute
    def _execute(self) -> List[dict]:
        retired: List[dict] = []
        for iid in list(self._live):
            inst = self._live[iid]
            dag = self.templates[inst.template]
            sim = self.sims[inst.template]
            ready = [s for s in dag.stages
                     if s.name not in inst.completions
                     and all(u in inst.completions
                             for u in dag.predecessors(s.name))]
            for s in ready:
                release = max((inst.completions[u]
                               for u in dag.predecessors(s.name)),
                              default=0.0)
                w = inst.weights[s.name]
                join_t, durs = sim.stage_sims[s.name].run_step(w)
                inst.completions[s.name] = release + join_t
                self.heads.observe(iid, f"{inst.template}/{s.name}",
                                   durs, w)
            if inst.completions:
                inst.elapsed = max(inst.completions.values())
            if len(inst.completions) == len(dag.stages):
                miss = inst.elapsed > inst.deadline
                self.telemetry.bump("retired")
                if miss:
                    self.telemetry.bump("slo_misses")
                self.telemetry.add("join_latency_s", inst.elapsed)
                retired.append({"iid": iid, "template": inst.template,
                                "join_latency_s": inst.elapsed,
                                "slo_miss": bool(miss),
                                "ticks_in_flight":
                                    self.tick_count - inst.admitted_tick})
                self.heads.retire(iid)
                del self._live[iid]
        return retired

    # ------------------------------------------------------------ tick
    def tick(self, arrivals=()) -> dict:
        """One engine tick: admit -> batched solve -> execute -> retire.

        ``arrivals``: template names (or ``(template, deadline)`` pairs) to
        submit before admission — convenience for trace-driven callers.
        """
        self.tick_count += 1
        obs.set_tick(self.tick_count)
        with obs.span(obs_names.SPAN_ENGINE_TICK) as sp_tick:
            for sim in self.sims.values():
                sim.tick()  # scheduled churn fires before this tick's draws
            for a in arrivals:
                if isinstance(a, (tuple, list)):
                    self.submit(a[0], a[1])
                else:
                    self.submit(a)
            with obs.span(obs_names.SPAN_ENGINE_STAGE, stage="admission"):
                admitted = self._admit()
            with obs.span(obs_names.SPAN_ENGINE_STAGE, stage="stack_rows"):
                rows = self._gather_rows()
            with obs.span(obs_names.SPAN_ENGINE_STAGE, stage="launch"):
                launches = self._solve_tick(rows) if rows else 0
            self.last_rows = rows
            with obs.span(obs_names.SPAN_ENGINE_STAGE, stage="commit"):
                retired = self._execute()
            self.telemetry.bump("ticks")
            self.telemetry.add("live_instances", len(self._live))
            self.last_tick = {
                "tick": self.tick_count,
                "admitted": admitted,
                "retired": retired,
                "live": len(self._live),
                "queue": len(self._queue),
                "rows": len(rows),
                "launches": launches,
            }
            if obs.enabled():
                sp_tick.attrs.update(live=len(self._live),
                                     queue=len(self._queue),
                                     rows=len(rows), launches=launches)
        return self.last_tick

    # ------------------------------------------------------------ state
    def state_dict(self) -> dict:
        """Everything the kill/restore tick-parity contract needs: the
        admission queue, every live instance (splits, progress, solve
        state), all estimation heads, every template's sim world (rng
        streams included) and the telemetry reservoirs. Templates stay
        code-side, like the workflow balancer's DAG."""
        return {
            "kind": "engine",
            "config": {
                "max_live": self.max_live, "lam_var": self.lam_var,
                "slo_gain": self.slo_gain, "slo_lam_cap": self.slo_lam_cap,
                "settle_steps": self.settle_steps,
                "dirty_tol": self.dirty_tol, "lr": self.lr,
                "num_t": self.num_t, "impl": self.impl, "seed": self.seed,
            },
            "tick_count": self.tick_count,
            "next_iid": self._next_iid,
            "queue": [dict(q) for q in self._queue],
            "instances": {str(iid): {
                "template": i.template,
                "deadline": (None if not np.isfinite(i.deadline)
                             else i.deadline),
                "admitted_tick": i.admitted_tick,
                "elapsed": i.elapsed,
                "completions": {k: float(v)
                                for k, v in i.completions.items()},
                "weights": {k: np.asarray(v).tolist()
                            for k, v in i.weights.items()},
                "stage_mu": dict(i.stage_mu),
                "stage_var": dict(i.stage_var),
                "steps_left": i.steps_left,
                "lam": i.lam,
                "stat_snap": {k: [np.asarray(m).tolist(),
                                  np.asarray(s).tolist()]
                              for k, (m, s) in i.stat_snap.items()},
            } for iid, i in self._live.items()},
            "heads": self.heads.state_dict(),
            "sims": {name: sim.state_dict()
                     for name, sim in self.sims.items()},
            "telemetry": self.telemetry.state_dict(),
        }

    def load_state_dict(self, d: dict) -> "WorkflowEngine":
        self.tick_count = int(d["tick_count"])
        self._next_iid = int(d["next_iid"])
        self._queue = deque(dict(q) for q in d.get("queue", []))
        self._live = {}
        for iid_s, s in d.get("instances", {}).items():
            iid = int(iid_s)
            inst = _Instance(
                iid=iid, template=s["template"],
                deadline=(float("inf") if s["deadline"] is None
                          else float(s["deadline"])),
                admitted_tick=int(s["admitted_tick"]),
                elapsed=float(s["elapsed"]),
                completions={k: float(v)
                             for k, v in s["completions"].items()},
                weights={k: np.asarray(v, np.float64)
                         for k, v in s["weights"].items()},
                stage_mu={k: float(v) for k, v in s["stage_mu"].items()},
                stage_var={k: float(v) for k, v in s["stage_var"].items()},
                steps_left=int(s["steps_left"]),
                lam=float(s["lam"]),
                stat_snap={k: (np.asarray(m, np.float64),
                               np.asarray(sg, np.float64))
                           for k, (m, sg) in s["stat_snap"].items()})
            self._live[iid] = inst
        self.heads = InstanceHeads.from_state_dict(d["heads"])
        self.sims = {name: WorkflowSim.from_state_dict(sd)
                     for name, sd in d["sims"].items()}
        self.telemetry = ServeTelemetry.from_state_dict(d["telemetry"])
        return self

    @classmethod
    def from_state_dict(cls, d: dict,
                        templates: Dict[str, object]) -> "WorkflowEngine":
        cfg = dict(d.get("config", {}))
        return cls(templates, **cfg).load_state_dict(d)
