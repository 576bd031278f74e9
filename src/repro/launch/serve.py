"""Serving CLI: paper-partitioned request batching across replica groups.

    PYTHONPATH=src python -m repro.launch.serve --batches 50 --requests 64 \
        --policy frontier

Replica-group service times come from simulated channels.

``--engine`` switches to the continuous-batching :class:`WorkflowEngine`:
instead of one replica fleet per batch, every tick admits queued workflow
instances (two templates, mixed families) and prices ALL their stage splits
through one stacked launch per family group:

    PYTHONPATH=src python -m repro.launch.serve --engine --batches 40 \
        --arrival-rate 8 --deadline 4.0
"""
import argparse

import numpy as np

from ..compile_cache import enable_compile_cache
from ..obs import trace as obs
from ..serve import PartitionedBatcher, ReplicaGroup
from ..sim.cluster import Channel, ClusterSim


def _engine_templates():
    from ..workflow.dag import Stage, StageDAG, linear_edges
    pipeline = StageDAG([
        Stage("prefill", mus=[1.0, 1.4, 1.9], sigmas=[0.2, 0.25, 0.35]),
        Stage("decode", mus=[2.0, 2.6, 3.3, 4.0],
              sigmas=[0.3, 0.4, 0.5, 0.6]),
    ], edges=linear_edges(["prefill", "decode"]))
    diamond = StageDAG([
        Stage("shard", mus=[1.2, 1.6, 2.1], sigmas=[0.25, 0.3, 0.4],
              family="lognormal"),
        Stage("rank_a", mus=[2.4, 3.0, 3.7], sigmas=[0.5, 0.6, 0.7],
              family="lognormal"),
        Stage("rank_b", mus=[2.1, 2.7, 3.4], sigmas=[0.45, 0.55, 0.65],
              family="lognormal"),
        Stage("blend", mus=[1.1, 1.5], sigmas=[0.2, 0.3],
              family="lognormal"),
    ], edges=[("shard", "rank_a"), ("shard", "rank_b"),
              ("rank_a", "blend"), ("rank_b", "blend")])
    return {"pipeline": pipeline, "diamond": diamond}


def _run_engine(args) -> None:
    from ..serve import WorkflowEngine
    templates = _engine_templates()
    eng = WorkflowEngine(templates, max_live=args.max_live, lam_var=0.02,
                         num_t=256, prior_obs=4)
    rng = np.random.default_rng(0)
    names = list(templates)
    for t in range(args.batches):
        arrivals = []
        for _ in range(int(rng.poisson(args.arrival_rate))):
            tpl = names[int(rng.integers(len(names)))]
            arrivals.append((tpl, args.deadline) if args.deadline else tpl)
        out = eng.tick(arrivals)
        if t % 10 == 0:
            print(f"tick {t:3d} live={out['live']} queue={out['queue']} "
                  f"rows={out['rows']} launches={out['launches']} "
                  f"retired={len(out['retired'])}")
    s = eng.telemetry.summary()
    c = s["counters"]
    print(f"engine: {c['ticks']} ticks, {c['retired']}/{c['admitted']} "
          f"retired, {c['slo_misses']} SLO misses, "
          f"{c['launches']} launches "
          f"(rows/launch p50 {s['rows_per_launch']['p50']:.0f})")
    print(f"join latency p50 {s['join_latency_s']['p50']:.3f}s "
          f"p99 {s['join_latency_s']['p99']:.3f}s; "
          f"solver tick p50 {s['solver_tick_us']['p50']:.0f}us")
    if args.trace:
        _export_trace(args.trace)


def _export_trace(prefix: str) -> None:
    """Dump the tracer's ring buffer as JSONL + a Perfetto-loadable trace.

    Writes ``<prefix>.jsonl`` and ``<prefix>.perfetto.json``; a no-op
    message is printed when tracing was never enabled (REPRO_TRACE unset),
    so --trace without the env var doesn't silently produce empty files.
    """
    from ..obs import export as obs_export
    recs = obs.records()
    if not recs:
        print("trace: no records captured — run with REPRO_TRACE=1")
        return
    jsonl = f"{prefix}.jsonl"
    perfetto = f"{prefix}.perfetto.json"
    obs_export.validate_records(recs)
    obs_export.write_jsonl(recs, jsonl)
    obs_export.write_perfetto(recs, perfetto)
    print(f"trace: {len(recs)} records "
          f"({len(obs_export.span_kinds(recs))} span kinds, "
          f"{len(obs_export.event_types(recs))} event types, "
          f"{obs.dropped()} dropped) -> {jsonl}, {perfetto}")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=50)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--policy", default="frontier",
                    choices=("frontier", "equal", "inverse_mu"))
    # closed-estimation-loop knobs (PR 4), threaded end-to-end into the
    # batcher's balancer: online family selection, risk-adjusted candidate
    # scoring, sensitivity-sized refresh cadence
    ap.add_argument("--family", default="normal",
                    choices=("normal", "lognormal", "drift", "auto"),
                    help="completion-time family for the frontier solve "
                         "(auto = online BIC selection with hysteresis)")
    ap.add_argument("--risk-lam", type=float, default=0.0,
                    help="fragility weight: candidates scored mu + lam var "
                         "+ risk_lam * estimation-fragility")
    ap.add_argument("--adaptive-refresh", action="store_true",
                    help="size the re-solve cadence by posterior "
                         "sensitivity instead of a fixed refresh_every")
    ap.add_argument("--refresh-every", type=int, default=1,
                    help="re-solve cadence cap (the adaptive mode "
                         "stretches toward this as estimates firm up)")
    # continuous-batching engine mode (PR 9)
    ap.add_argument("--engine", action="store_true",
                    help="serve workflow instances through the "
                         "continuous-batching WorkflowEngine instead of "
                         "the per-batch PartitionedBatcher")
    ap.add_argument("--max-live", type=int, default=64,
                    help="engine mode: live-instance capacity")
    ap.add_argument("--arrival-rate", type=float, default=6.0,
                    help="engine mode: mean Poisson arrivals per tick")
    ap.add_argument("--deadline", type=float, default=None,
                    help="engine mode: SLO deadline (sim seconds) attached "
                         "to every request")
    # cross-layer tracing (PR 10)
    ap.add_argument("--trace", default=None, metavar="PREFIX",
                    help="export the run's trace to PREFIX.jsonl and "
                         "PREFIX.perfetto.json (enables tracing for the "
                         "run; REPRO_TRACE=1 also works)")
    args = ap.parse_args()
    if args.trace:
        obs.set_enabled(True)

    if args.engine:
        _run_engine(args)
        return

    groups = [ReplicaGroup("fast"), ReplicaGroup("slow")]
    sim = ClusterSim([Channel(mu=20.0, sigma=2.0), Channel(mu=14.0, sigma=5.0)])
    b = PartitionedBatcher(groups, policy=args.policy, sim=sim,
                           family=args.family, risk_lam=args.risk_lam,
                           adaptive_refresh=args.adaptive_refresh,
                           refresh_every=args.refresh_every)
    lat = []
    requests = np.arange(args.requests)
    for i in range(args.batches):
        t, counts = b.run_batch(requests)
        lat.append(t)
        if i % 10 == 0:
            tick = b.last_tick
            print(f"batch {i:3d} split={counts.tolist()} join={t:.2f}s "
                  f"family={tick['family']} "
                  f"refresh={tick['effective_refresh']}")
    lat = np.asarray(lat)
    print(f"policy={args.policy} family={args.family} "
          f"risk_lam={args.risk_lam}: mean join {lat.mean():.3f}s  "
          f"var {lat.var():.4f}  p99 {np.percentile(lat, 99):.3f}s")
    if args.trace:
        _export_trace(args.trace)


if __name__ == "__main__":
    main()
