"""Beyond-paper: the partitioner inside a real training loop.

Runs a few hundred simulated training steps on 2 heterogeneous pods
(one fast/stable, one slow/noisy) under three scheduling policies and compares
realized per-step join times AND training throughput (tokens/s against the
simulated clock). This is paper Fig 3/4 logic transplanted onto the gradient
pipeline: the join is the cross-pod gradient reduction.
"""
import numpy as np

from .common import emit, save_table


def _run(policy: str, steps: int = 150, seed: int = 0):
    from repro.sched import UncertaintyAwareBalancer
    from repro.sim import Channel, ClusterSim

    # per-pod sec per *microbatch*: pod0 fast+stable, pod1 slow+noisy
    sim = ClusterSim([Channel(mu=0.9, sigma=0.05), Channel(mu=1.5, sigma=0.45)],
                     seed=seed)
    bal = UncertaintyAwareBalancer(2, lam=0.05, policy=policy)
    total_micro = 8
    join_times, done = [], 0
    for i in range(steps):
        k = bal.assign(total_micro)
        # run_step normalizes counts to batch fractions; channel rates are
        # sec per *microbatch*, so scale the realized times back to seconds
        t, durs = sim.run_step(k.astype(np.float64))
        t, durs = t * total_micro, durs * total_micro
        bal.observe(durs, k.astype(np.float64))
        if i >= 20:
            join_times.append(t)
            done += int(k.sum())
    jt = np.asarray(join_times)
    return jt.mean(), jt.var(), done / jt.sum()


def run() -> dict:
    rows = []
    res = {}
    for policy in ("equal", "inverse_mu", "frontier"):
        mu, var, thr = _run(policy)
        rows.append((policy, mu, var, thr))
        res[policy] = (mu, var, thr)
        emit(f"parttrain_{policy}", mu * 1e6,
             f"join_var={var:.4f};microbatches_per_s={thr:.3f}")
    save_table("partitioned_training.csv", "policy,join_mu,join_var,micro_per_s",
               rows)
    assert res["frontier"][0] < res["equal"][0]
    assert res["frontier"][2] > res["equal"][2]  # higher throughput
    return res


if __name__ == "__main__":
    print(run())
