"""Serving-path end-to-end benchmark: the continuous-batching
:class:`~repro.serve.engine.WorkflowEngine` under a bursty request trace.

Traffic is Poisson arrivals over THREE workflow templates spanning three
completion-time families (normal ETL, lognormal training diamond, drifting
media pipeline); the arrival rate switches between a calm and a burst
regime (two-state Markov chain, seeded) and each switch also moves the
fleet-wide congestion factor of every template's sim world
(``WorkflowEngine.set_load``). A stage-addressed churn schedule
(``WorkflowSim.schedule_churn``) throttles and fails channels mid-trace, so
the engine faces non-stationary statistics exactly where the per-instance
estimation heads and the dirty-instance re-solve protocol earn their keep.

The headline number is ``batched_vs_looped_ratio``: at sampled ticks the
engine's actual row set (``engine.last_rows``) is solved twice — once the
engine's way (ONE stacked ``row_pgd_step`` launch per family group) and
once as the per-instance loop this engine replaced (one launch per live
workflow). Both paths are warmed before timing so the ratio compares
steady-state dispatch cost, not compilation. The full-scale run holds >=256
concurrent live instances and ``scripts/ci.sh`` asserts the ratio >= 4
there.

``--json`` writes machine-readable ``BENCH_serve_trace.json`` at the repo
root (schema: ``SCHEMA_KEYS`` below — join-latency percentiles from the
engine's streaming reservoirs, solver-tick wall-clock, rows-per-launch
occupancy, live-instance high-water mark, SLO verdicts, per-regime
latency); ``scripts/bench_smoke.sh`` runs the small config and
``scripts/ci.sh`` asserts the schema keys and the acceptance gates.

Under ``REPRO_TRACE=1`` the run also exports its full cross-layer trace
(``TRACE_serve_trace*.jsonl`` + a Perfetto-loadable ``.perfetto.json``) and
adds a ``trace`` section to the JSON: record counts, the span kinds and
audit event types observed, and the traced-vs-untraced solver wall-clock
overhead the zero-perturbation contract bounds below 5% (ci.sh's ``trace``
tier asserts all of it).
"""
import argparse
import json
import os

import numpy as np

from .common import emit, save_table, timeit

TICKS = 120
SMOKE_TICKS = 24
MAX_LIVE = 320          # live-set capacity (full scale: >=256 held live)
SMOKE_MAX_LIVE = 48
PREFILL = 400           # requests queued before tick 1 fills the live set
SMOKE_PREFILL = 64
LAM_CALM = 24.0         # mean arrivals/tick, calm regime
LAM_BURST = 96.0        # mean arrivals/tick, burst regime
P_ENTER_BURST = 0.05    # per-tick calm -> burst probability
P_EXIT_BURST = 0.15    # per-tick burst -> calm probability
BURST_LOAD = 1.6        # fleet-wide congestion factor while bursting
RATIO_SAMPLES = 3       # ticks whose row set is re-timed batched vs looped
NUM_T = 128

# the machine-readable contract of BENCH_serve_trace*.json — declared next
# to the writer; scripts/ci.sh imports these to validate the emitted files
SCHEMA_KEYS = ("bench", "smoke", "ticks", "templates", "max_live",
               "latency", "solver_tick_us", "rows_per_launch",
               "row_occupancy", "live_instances", "queue_wait_ticks",
               "batched_vs_looped_ratio", "slo", "regimes", "counters",
               "entries")
ENTRY_KEYS = ("name", "family", "ticks", "mean_s", "var_s2", "p99_s")


def _templates() -> dict:
    """Three workflow shapes across three completion-time families."""
    from repro.core.distributions import Drift
    from repro.workflow.dag import Stage, StageDAG, linear_edges

    etl = StageDAG([
        Stage("extract", mus=[1.0, 1.3, 1.7, 2.2, 2.6, 3.0],
              sigmas=[0.20, 0.25, 0.30, 0.40, 0.45, 0.50]),
        Stage("transform", mus=[2.0, 2.4, 3.0, 3.5],
              sigmas=[0.30, 0.35, 0.50, 0.55]),
        Stage("load", mus=[1.1, 1.6, 2.1], sigmas=[0.20, 0.30, 0.35]),
    ], edges=linear_edges(["extract", "transform", "load"]))
    train = StageDAG([
        Stage("prep", mus=[1.5, 1.9, 2.3, 2.8],
              sigmas=[0.30, 0.35, 0.40, 0.50], family="lognormal"),
        Stage("fit_a", mus=[2.5, 3.0, 3.6, 4.2, 4.9],
              sigmas=[0.50, 0.60, 0.70, 0.80, 0.90], family="lognormal"),
        Stage("fit_b", mus=[2.2, 2.8, 3.3, 3.9, 4.5],
              sigmas=[0.45, 0.55, 0.65, 0.75, 0.85], family="lognormal"),
        Stage("merge", mus=[1.2, 1.7, 2.2], sigmas=[0.25, 0.30, 0.40],
              family="lognormal"),
    ], edges=[("prep", "fit_a"), ("prep", "fit_b"),
              ("fit_a", "merge"), ("fit_b", "merge")])
    media = StageDAG([
        Stage("render", mus=[1.8, 2.2, 2.7, 3.2, 3.8, 4.4],
              sigmas=[0.35, 0.40, 0.50, 0.60, 0.70, 0.80],
              family=Drift(0.35)),
        Stage("encode", mus=[1.4, 1.8, 2.3, 2.9],
              sigmas=[0.25, 0.30, 0.40, 0.50], family=Drift(0.20)),
    ], edges=linear_edges(["render", "encode"]))
    return {"etl": etl, "train": train, "media": media}


def _naive_makespan(dag) -> float:
    """Longest path of equal-split stage means — the deadline yardstick."""
    lp = {}
    for name in dag.topo_order:
        s = dag.stages[dag.names.index(name)]
        rel = max((lp[u] for u in dag.predecessors(name)), default=0.0)
        lp[name] = rel + float(np.mean(s.mus)) / s.k
    return max(lp.values())


def _launch_rows(rows, kmax: int, num_t: int, impl: str) -> list:
    """Solve one row set the engine's way: stack, pad to the row bucket,
    ONE ``row_pgd_step`` launch per family group. Mirrors
    ``WorkflowEngine._solve_tick`` so the timed work is the same.

    Returns one ``(idx, mu, var, W_next)`` per family group: the group's
    indices into ``rows`` and the launch's outputs for those real rows
    (bucket padding sliced off)."""
    from repro.kernels import autotune
    from repro.serve.engine import row_pgd_step
    from repro.workflow.solve import stack_rows

    groups, mask, km = stack_rows(
        [(r.mus, r.sigmas, r.family) for r in rows], kmax=kmax)
    outs = []
    for g in groups:
        n = len(g.idx)
        F = autotune.bucket_rows(n)
        E = g.extra.shape[0]
        W = np.zeros((F, km), np.float32)
        mus = np.zeros((F, km), np.float32)
        sgs = np.zeros((F, km), np.float32)
        ex = np.zeros((E, F, km), np.float32)
        msk = np.zeros((F, km), np.float32)
        lam = np.zeros(F, np.float32)
        for j, ridx in enumerate(g.idx):
            r = rows[ridx]
            W[j, :r.k] = r.w
            msk[j] = mask[ridx]
            lam[j] = r.lam
        mus[:n], sgs[:n], ex[:, :n] = g.mus, g.sigmas, g.extra
        if F > n:
            W[n:], mus[n:], sgs[n:] = W[0], mus[0], sgs[0]
            ex[:, n:] = ex[:, :1]
            msk[n:], lam[n:] = msk[0], lam[0]
        m, v, W2 = row_pgd_step(W, mus, sgs, g.dist_id, ex, lam, msk,
                                num_t=num_t, impl=impl)
        outs.append((g.idx, m[:n], v[:n], W2[:n]))
    return outs


def _solve_batched(rows, kmax: int, num_t: int, impl: str) -> None:
    _launch_rows(rows, kmax, num_t, impl)


def _solve_looped(rows, kmax: int, num_t: int, impl: str) -> None:
    """The pre-engine baseline: one launch per live workflow instance (the
    per-instance Python loop RPA080 bans under serve/ — legal here as the
    documented benchmark baseline, outside the serving path)."""
    by_iid = {}
    for r in rows:
        by_iid.setdefault(r.iid, []).append(r)
    for inst_rows in by_iid.values():
        _launch_rows(inst_rows, kmax, num_t, impl)


def _measure_ratio(rows, kmax: int, num_t: int, impl: str):
    """(batched_us, looped_us) on one captured row set, compile excluded
    (``timeit`` warms each path before timing)."""
    b_us = timeit(_solve_batched, rows, kmax, num_t, impl,
                  repeats=3, warmup=1)
    l_us = timeit(_solve_looped, rows, kmax, num_t, impl,
                  repeats=3, warmup=1)
    return b_us, l_us


def _trace_overhead_pct(rows, kmax: int, num_t: int, impl: str) -> float:
    """Traced-vs-untraced wall-clock on the engine's own solver work.

    Times the stacked ``row_pgd_step`` dispatch (the hot path every tick
    pays) with tracing force-disabled, then force-enabled, min-of-repeats
    each so scheduler noise doesn't masquerade as tracing cost. This is
    the number the zero-perturbation contract bounds (< 5%); ci.sh's
    trace tier asserts it.
    """
    from repro.obs import trace as obs

    def best(repeats=5):
        return min(timeit(_solve_batched, rows, kmax, num_t, impl,
                          repeats=1, warmup=1) for _ in range(repeats))

    was = obs.enabled()
    try:
        obs.set_enabled(False)
        off_us = best()
        obs.set_enabled(True)
        on_us = best()
    finally:
        obs.set_enabled(was)
    return 100.0 * (on_us - off_us) / max(off_us, 1e-9)


def run(ticks: int = TICKS, seed: int = 0, smoke: bool = False) -> dict:
    from repro.serve.engine import WorkflowEngine

    templates = _templates()
    max_live = SMOKE_MAX_LIVE if smoke else MAX_LIVE
    prefill = SMOKE_PREFILL if smoke else PREFILL
    lam_calm = LAM_CALM / 4 if smoke else LAM_CALM
    lam_burst = LAM_BURST / 4 if smoke else LAM_BURST
    eng = WorkflowEngine(templates, max_live=max_live, lam_var=0.02,
                         slo_gain=0.5, settle_steps=4, dirty_tol=0.08,
                         num_t=NUM_T, seed=seed, prior_obs=4)

    # stage-addressed churn mid-trace: a throttled channel, a hard failure
    # with recovery, and a template-local load regime — the estimation heads
    # watch the world move under them
    t1, t2, t3 = max(2, ticks // 4), max(3, ticks // 2), max(4, 3 * ticks // 4)
    eng.sims["etl"].schedule_churn(t1, "throttle", stage="extract", idx=1,
                                   value=2.0)
    eng.sims["etl"].schedule_churn(t3, "recover", stage="extract", idx=1)
    eng.sims["train"].schedule_churn(t2, "fail", stage="fit_a", idx=0)
    eng.sims["train"].schedule_churn(t3, "recover", stage="fit_a", idx=0)
    eng.sims["media"].schedule_churn(t2, "set_load", value=1.3)
    eng.sims["media"].schedule_churn(t3, "set_load", value=1.0)

    rng = np.random.default_rng(seed)
    names = list(templates)
    est = {n: _naive_makespan(d) for n, d in templates.items()}

    def _request():
        tpl = names[int(rng.integers(len(names)))]
        # half the traffic carries an SLO deadline scaled off the naive
        # makespan: tight ones miss under burst load, loose ones never do
        if rng.random() < 0.5:
            return (tpl, est[tpl] * float(rng.uniform(0.8, 2.5)))
        return tpl

    for _ in range(prefill):
        req = _request()
        if isinstance(req, tuple):
            eng.submit(req[0], req[1])
        else:
            eng.submit(req)

    burst = False
    reg_joins = {"calm": [], "burst": []}
    tpl_joins = {n: [] for n in names}
    trace_rows = []
    batched_us = looped_us = 0.0
    samples = 0
    sample_every = max(3, ticks // (RATIO_SAMPLES + 1))
    for t in range(ticks):
        if burst and rng.random() < P_EXIT_BURST:
            burst = False
            eng.set_load(1.0)
        elif not burst and rng.random() < P_ENTER_BURST:
            burst = True
            eng.set_load(BURST_LOAD)
        lam = lam_burst if burst else lam_calm
        arrivals = [_request() for _ in range(int(rng.poisson(lam)))]
        out = eng.tick(arrivals)
        regime = "burst" if burst else "calm"
        for r in out["retired"]:
            reg_joins[regime].append(r["join_latency_s"])
            tpl_joins[r["template"]].append(r["join_latency_s"])
        trace_rows.append((t, regime, len(arrivals), out["admitted"],
                           out["live"], out["queue"], out["rows"],
                           out["launches"]))
        # re-time this tick's actual row set batched vs per-instance-looped
        if (samples < RATIO_SAMPLES and t >= 2 and eng.last_rows
                and (t + 1) % sample_every == 0
                and len({r.iid for r in eng.last_rows}) >= 4):
            b_us, l_us = _measure_ratio(eng.last_rows, eng.kmax,
                                        NUM_T, eng.impl)
            batched_us += b_us
            looped_us += l_us
            samples += 1

    assert samples > 0, "trace never yielded a sampleable row set"
    ratio = looped_us / max(batched_us, 1e-9)
    tel = eng.telemetry.summary()
    counters = tel.pop("counters")
    save_table("serve_trace_smoke.csv" if smoke else "serve_trace.csv",
               "tick,regime,arrivals,admitted,live,queue,rows,launches",
               trace_rows)
    reg = {name: {"ticks": int(sum(1 for r in trace_rows if r[1] == name)),
                  "latency_mean": (float(np.mean(js)) if js else None)}
           for name, js in reg_joins.items()}
    out = {
        "bench": "serve_trace",
        "smoke": smoke,
        "ticks": ticks,
        "templates": {n: {"stages": len(d.stages),
                          "family": d.stages[0].dist_id,
                          "retired": len(tpl_joins[n])}
                      for n, d in templates.items()},
        "max_live": max_live,
        "latency": tel["join_latency_s"],
        "solver_tick_us": tel["solver_tick_us"],
        "rows_per_launch": tel["rows_per_launch"],
        "row_occupancy": tel["row_occupancy"],
        "live_instances": tel["live_instances"],
        "queue_wait_ticks": tel["queue_wait_ticks"],
        "batched_vs_looped_ratio": float(round(ratio, 3)),
        "slo": {
            "misses": counters["slo_misses"],
            "retired": counters["retired"],
            "miss_rate": (counters["slo_misses"] / counters["retired"]
                          if counters["retired"] else 0.0),
        },
        "regimes": reg,
        "counters": counters,
        "entries": [
            {"name": f"serve_join_{n}", "family": d.stages[0].dist_id,
             "ticks": ticks,
             "mean_s": (float(np.mean(tpl_joins[n]))
                        if tpl_joins[n] else 0.0),
             "var_s2": (float(np.var(tpl_joins[n]))
                        if tpl_joins[n] else 0.0),
             "p99_s": (float(np.percentile(tpl_joins[n], 99))
                       if tpl_joins[n] else 0.0)}
            for n, d in templates.items()
        ],
    }
    emit("serve_engine_solver_tick_us", tel["solver_tick_us"]["p50"],
         f"rows_p50={tel['rows_per_launch']['p50']};"
         f"live_max={tel['live_instances']['max']}")
    emit("serve_engine_batched_vs_looped", ratio,
         f"samples={samples};launches={counters['launches']}")

    # cross-layer trace section (PR 10): only when the run was traced
    # (REPRO_TRACE=1). Exports the whole trace as JSONL + Perfetto at the
    # repo root, validates it against the event schema, and measures the
    # traced-vs-untraced solver overhead the zero-perturbation contract
    # bounds. Conditional so untraced runs keep the exact prior schema.
    from repro.obs import trace as obs
    if obs.enabled():
        from repro.obs import export as obs_export
        recs = obs.records()
        obs_export.validate_records(recs)
        root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
        suffix = "_smoke" if smoke else ""
        jsonl = os.path.join(root, f"TRACE_serve_trace{suffix}.jsonl")
        perfetto = os.path.join(root,
                                f"TRACE_serve_trace{suffix}.perfetto.json")
        obs_export.write_jsonl(recs, jsonl)
        obs_export.write_perfetto(recs, perfetto)
        overhead = _trace_overhead_pct(eng.last_rows, eng.kmax, NUM_T,
                                       eng.impl)
        out["trace"] = {
            "records": len(recs),
            "dropped": obs.dropped(),
            "span_kinds": sorted(obs_export.span_kinds(recs)),
            "event_types": sorted(obs_export.event_types(recs)),
            "overhead_pct": float(round(overhead, 3)),
            "jsonl": os.path.basename(jsonl),
            "perfetto": os.path.basename(perfetto),
        }
        emit("serve_engine_trace_overhead_pct", overhead,
             f"records={len(recs)};"
             f"span_kinds={len(out['trace']['span_kinds'])};"
             f"event_types={len(out['trace']['event_types'])}")
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="emit machine-readable BENCH_serve_trace.json")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced scale (fewer ticks, smaller live set)")
    ap.add_argument("--ticks", type=int, default=None)
    ap.add_argument("--out", default=None,
                    help="JSON output path (default: repo-root "
                         "BENCH_serve_trace.json, or _smoke variant)")
    args = ap.parse_args()

    ticks = args.ticks or (SMOKE_TICKS if args.smoke else TICKS)
    res = run(ticks=ticks, smoke=args.smoke)
    if args.json:
        root = os.path.join(os.path.dirname(__file__), "..")
        default = ("BENCH_serve_trace_smoke.json" if args.smoke
                   else "BENCH_serve_trace.json")
        path = args.out or os.path.abspath(os.path.join(root, default))
        with open(path, "w") as f:
            json.dump(res, f, indent=1, sort_keys=True)
        print(f"wrote {path}")
    print({k: res[k] for k in ("latency", "batched_vs_looped_ratio",
                               "live_instances", "slo")})
    if "trace" in res:
        print({"trace": res["trace"]})


if __name__ == "__main__":
    main()
