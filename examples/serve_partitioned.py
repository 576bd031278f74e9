"""Serving driver: batched requests partitioned across two replica groups by
the paper's frontier — the file-transfer experiment (Figs 5/6) as a serving
system. Simulated replica-speed physics, online learning of the split.

Run:  PYTHONPATH=src python examples/serve_partitioned.py --batches 60

``--engine`` demos the continuous-batching tier instead: many concurrent
workflow instances (mixed templates, SLO deadlines) admitted from a queue,
every dirty instance's remaining stages priced by ONE stacked launch per
completion-time family per tick, including a mid-trace kill/restore through
the checkpoint manifest.

Run:  PYTHONPATH=src python examples/serve_partitioned.py --engine
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np


def run_engine_demo(ticks: int = 30) -> None:
    """Continuous batching + SLO urgency + kill/restore, end to end."""
    import tempfile

    from repro.ckpt.store import restore_pipeline, save_pipeline
    from repro.serve import WorkflowEngine
    from repro.workflow.dag import Stage, StageDAG, linear_edges

    templates = {
        "etl": StageDAG([
            Stage("extract", mus=[1.0, 1.4, 1.9], sigmas=[0.2, 0.25, 0.35]),
            Stage("load", mus=[1.3, 1.8], sigmas=[0.25, 0.35]),
        ], edges=linear_edges(["extract", "load"])),
        "train": StageDAG([
            Stage("prep", mus=[1.5, 2.0, 2.6], sigmas=[0.3, 0.4, 0.5],
                  family="lognormal"),
            Stage("fit", mus=[2.4, 3.1, 3.9, 4.8],
                  sigmas=[0.5, 0.6, 0.7, 0.9], family="lognormal"),
        ], edges=linear_edges(["prep", "fit"])),
    }
    eng = WorkflowEngine(templates, max_live=32, lam_var=0.02, prior_obs=4)
    rng = np.random.default_rng(7)
    names = list(templates)
    with tempfile.TemporaryDirectory() as ckpt:
        for t in range(ticks):
            arrivals = [(names[int(rng.integers(2))],
                         float(rng.uniform(1.5, 4.0)))
                        for _ in range(int(rng.poisson(4.0)))]
            out = eng.tick(arrivals)
            save_pipeline(ckpt, eng.tick_count, eng)
            if t == ticks // 2:
                # the crash: drop the engine mid-flight, restore the
                # manifest — live instances, queue, heads, sims and all
                print(f"-- kill/restore at tick {out['tick']} "
                      f"({out['live']} instances in flight) --")
                eng, _, _ = restore_pipeline(ckpt, templates=templates)
            if t % 5 == 0:
                print(f"tick {out['tick']:3d}: live={out['live']} "
                      f"queue={out['queue']} rows={out['rows']} "
                      f"launches={out['launches']}")
    s = eng.telemetry.summary()
    c = s["counters"]
    print(f"\nengine summary: {c['retired']} retired / {c['admitted']} "
          f"admitted, {c['slo_misses']} SLO misses")
    print(f"join latency p50 {s['join_latency_s']['p50']:.3f}s "
          f"p99 {s['join_latency_s']['p99']:.3f}s; "
          f"{c['launches']} stacked launches over {c['ticks']} ticks "
          f"(rows/launch p50 {s['rows_per_launch']['p50']:.0f})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=60)
    ap.add_argument("--requests", type=int, default=64)
    # PR 4 estimation-loop knobs, exposed end-to-end: the balancer behind
    # the batcher accepts these; the example now lets you drive them
    ap.add_argument("--family", default="normal",
                    choices=("normal", "lognormal", "drift", "auto"),
                    help="completion-time family (auto = BIC-select online)")
    ap.add_argument("--risk-lam", type=float, default=0.0,
                    help="estimation-fragility weight in candidate scoring")
    ap.add_argument("--adaptive-refresh", action="store_true",
                    help="sensitivity-sized re-solve cadence")
    ap.add_argument("--refresh-every", type=int, default=1,
                    help="re-solve cadence (cap when adaptive)")
    ap.add_argument("--engine", action="store_true",
                    help="demo the continuous-batching WorkflowEngine "
                         "(admission queue, stacked launches, kill/restore)")
    ap.add_argument("--ticks", type=int, default=30,
                    help="engine mode: trace length")
    args = ap.parse_args()

    if args.engine:
        run_engine_demo(ticks=args.ticks)
        return

    from repro.serve import PartitionedBatcher, ReplicaGroup
    from repro.sim import Channel, ClusterSim

    groups = [ReplicaGroup("overlay-path"), ReplicaGroup("direct-path")]
    requests = np.arange(args.requests)

    results = {}
    for policy in ("equal", "frontier"):
        sim = ClusterSim([Channel(24.0, 1.6), Channel(18.0, 4.8)], seed=11)
        batcher = PartitionedBatcher(groups, lam=0.08, policy=policy, sim=sim,
                                     family=args.family,
                                     risk_lam=args.risk_lam,
                                     adaptive_refresh=args.adaptive_refresh,
                                     refresh_every=args.refresh_every)
        lat = []
        for i in range(args.batches):
            t, counts = batcher.run_batch(requests)
            lat.append(t)
            if i % 20 == 0:
                tick = batcher.last_tick
                print(f"[{policy}] batch {i:3d}: split={counts.tolist()} "
                      f"join={t:.2f}s family={tick['family']} "
                      f"refresh={tick['effective_refresh']}")
        lat = np.asarray(lat[10:])
        results[policy] = lat
        print(f"[{policy}] mean={lat.mean():.3f}s var={lat.var():.4f} "
              f"p99={np.percentile(lat, 99):.3f}s\n")

    imp_mu = 1 - results["frontier"].mean() / results["equal"].mean()
    imp_var = 1 - results["frontier"].var() / results["equal"].var()
    print(f"frontier vs equal: mean latency -{imp_mu:.1%}, variance -{imp_var:.1%}")


if __name__ == "__main__":
    main()
