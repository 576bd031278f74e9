"""The system under test for configurations solved by ``repro.workflow.solve.solve_dag``.

The configuration file gives a workflow of lanes (``workflow``): each lane
a chain of jobs, the lanes joined by a chain of tail jobs. A job named in
``chunked`` is one stage whose work is shared over the lane's chunks, its
channels; any other job is a stage of one channel. Every channel's mean is
the job's mean runtime (``mean_s``) times a node factor, and its standard
deviation a share of its mean, both drawn from the seed. The traffic mix is
a closed loop of one caller: each request rescales the statistics of every
stage by a factor of its own and asks for a cold solve.

What the window produced is checked on a sample of its solves drawn from
the seed: the returned stage moments against the float64 quadrature of
``bench/reference/frontier.py`` at the returned splits, the makespan
moments against the reference's Clark composition of those, the splits
against the simplex, and the returned objective against the best plain
split (equal shares or shares inverse to the channel means). A Monte Carlo
run of the DAG at the returned splits is printed beside the composed
makespan.
"""
from __future__ import annotations

import time

import numpy as np

from bench import traffic

# warm-up requests after the mix's own, while they still compile something
MORE_WARM = 6


def lane_chunks(wf: dict) -> list:
    """Chunks of each lane: ``chunks`` dealt to the lanes in turn."""
    n, c = wf["lanes"], wf["chunks"]
    return [c // n + (i < c % n) for i in range(n)]


def make_dag(config: dict, seed: int):
    """(names, edges, mus, sigmas) from the seed; ``mus[i]`` and
    ``sigmas[i]`` are stage ``i``'s channel statistics (arrays of its own
    channel count)."""
    wf = config["workflow"]
    rng = np.random.default_rng([seed, 3])
    names, edges, jobs, ks = [], [], [], []

    def add(name, job, k, prev):
        names.append(name)
        jobs.append(job)
        ks.append(k)
        if prev is not None:
            edges.append((prev, name))
        return name

    ends = []
    for lane, chunks in enumerate(lane_chunks(wf)):
        prev = None
        for job in wf["lane"]:
            k = chunks if job in wf["chunked"] else 1
            prev = add(f"{job}.{lane}", job, k, prev)
        ends.append(prev)
    first = wf["tail"][0]
    add(first, first, 1, None)
    edges.extend((e, first) for e in ends)
    prev = first
    for job in wf["tail"][1:]:
        prev = add(job, job, 1, prev)
    mus, sigmas = [], []
    for job, k in zip(jobs, ks):
        mu = k * wf["mean_s"][job] * rng.uniform(*wf["node_factor"], k)
        mus.append(mu)
        sigmas.append(mu * rng.uniform(*wf["sigma_ratio"], k))
    return names, edges, mus, sigmas


def _stage_dag(names, edges, mus, sigmas, family):
    from repro.workflow.dag import Stage, StageDAG
    return StageDAG([Stage(n, mus[i], sigmas[i], family=family)
                     for i, n in enumerate(names)], edges)


def _scaled(stats, f):
    return [s * fi for s, fi in zip(stats, f)]


def run(config: dict, mix: dict, seed: int, window) -> dict:
    from repro.workflow.solve import solve_dag

    names, edges, mus0, sg0 = make_dag(config, seed)
    family = config["workflow"]["family"]
    S = len(names)

    def request(rq):
        dag = _stage_dag(names, edges, _scaled(mus0, rq.factors),
                         _scaled(sg0, rq.factors), family)
        return rq.factors, solve_dag(dag, **config["solve"])

    warm_gen = traffic.closed_loop(mix, np.random.default_rng([seed, 1]), S)
    for i in range(mix["warm_requests"] + MORE_WARM):
        before = len(window.compile_times)
        request(next(warm_gen))
        if i >= mix["warm_requests"] and len(window.compile_times) == before:
            break

    gen = traffic.closed_loop(mix, np.random.default_rng([seed, 2]), S)
    log, ends = [], []
    ends.append(window.open())
    while True:
        rq = next(gen)
        with window.annotate("bench.solve"):
            log.append(request(rq))
        ends.append(time.perf_counter())
        if window.step():
            break
    nt = config["solve"]["num_t"]
    return {
        "kind": "dag",
        "attempted": len(log),
        "solves": len(log),
        "log": log,
        "dag": (names, edges, mus0, sg0, family),
        "phase_us": [dec.profile.get("phase_us", {}) for _, dec in log],
        "solve_times_ms": (np.diff(ends) * 1e3).tolist(),
        "ladder_t": ladder_t(log[0][1].profile, nt),
        "notes": [f"dag: {len(log)} solves in the window, methods "
                  f"{sorted({d.method for _, d in log})}, survivors "
                  f"{sorted({d.profile.get('survivors') for _, d in log})}",
                  pace(ends)],
    }


def pace(ends: list) -> str:
    """How steady the window ran: the solves that ended in each fifth of
    it, and quantiles of the time one solve took (a stall of the host or
    the device shows as one long solve)."""
    t = np.asarray(ends)
    fifths = np.histogram(t[1:], np.linspace(t[0], t[-1], 6))[0]
    ms = np.quantile(np.diff(t) * 1e3, [0.5, 0.9, 0.99, 1.0])
    return (f"dag pace: solves per fifth of the window {fifths.tolist()}; "
            f"ms a solve p50 {ms[0]:.2f} p90 {ms[1]:.2f} p99 {ms[2]:.2f} "
            f"max {ms[3]:.2f}")


def ladder_t(profile: dict, num_t: int) -> dict:
    """Grid points of each rung of the solver's ladder: the presolve and
    triage, the refine, the final score."""
    return {"presolve": profile["presolve_num_t"], "refine": num_t,
            "final": profile["eval_num_t"]}


def _padded(rows, kmax: int) -> np.ndarray:
    """Ragged per-stage rows as one (stages, kmax) array, zero-padded."""
    out = np.zeros((len(rows), kmax))
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out


def _objective(names, edges, W, mus, sigmas, mask, family, num_t, lam_var,
               dtype):
    """Makespan (mean, var) and objective of split W under the reference."""
    from bench.reference import dag as rdag
    from bench.reference import frontier as ref

    rho = np.zeros(W.shape)
    sm, sv = ref.stage_moments(*ref.as_dtype([W, mus, sigmas, rho, mask],
                                             dtype),
                               family=family, num_t=num_t)
    sm = np.asarray(sm, np.float64)
    sv = np.asarray(sv, np.float64)
    m, v = rdag.makespan(names, edges, sm, sv, dtype)
    return sm, sv, m, v, m + lam_var * v


def compare(record: dict, config: dict, seed: int,
            dtype_name: str = "float64"):
    """Numbers of the program (``float64``) or of the control (the reference
    at ``dtype_name``, in the program's place) on the sampled solves;
    returns (numbers, indices of the solves over a limit)."""
    import jax
    import jax.numpy as jnp

    from bench.reference import dag as rdag
    from bench.reference import float64

    chk = config["check"]
    names, edges, mus0, sg0, family = record["dag"]
    log = record["log"]
    rng = np.random.default_rng([seed, 4])
    pick = sorted(rng.choice(len(log), min(len(log), chk["solves"]),
                             replace=False).tolist())
    lam_var = float(config["solve"].get("lam_var", 0.0))
    nt = chk["eval_num_t"]
    num = {"stage_mu": 0.0, "stage_var": 0.0, "makespan_mu": 0.0,
           "makespan_var": 0.0, "simplex": 0.0, "descent": 0.0}
    ks = [len(m) for m in mus0]
    K = max(ks)
    mask = _padded([np.ones(k) for k in ks], K) > 0
    bad, notes = set(), []
    cpu = jax.devices("cpu")[0]
    with float64(), jax.default_device(cpu):
        for i in pick:
            f, dec = log[i]
            mus_i, sgs_i = _scaled(mus0, f), _scaled(sg0, f)
            mus, sgs = _padded(mus_i, K), _padded(sgs_i, K)
            W = _padded([np.asarray(dec.weights[n], np.float64)
                         for n in names], K)
            args = (mus, sgs, mask, family, nt, lam_var)
            sm, sv, m, v, obj = _objective(names, edges, W, *args,
                                           jnp.float64)
            if dtype_name == "float64":
                p_sm = np.asarray(dec.stage_mu, np.float64)
                p_sv = np.asarray(dec.stage_var, np.float64)
                p_m, p_v = float(dec.makespan_mu), float(dec.makespan_var)
                p_W = W
            else:
                # the control returns its splits in its own precision too
                dt = jnp.dtype(dtype_name)
                p_W = np.asarray(jnp.asarray(W, dt), np.float64)
                p_sm, p_sv, p_m, p_v, _ = _objective(names, edges, p_W,
                                                     *args, dt)
            inv = np.where(mask, 1.0 / np.where(mask, mus, 1.0), 0.0)
            starts = [mask / np.asarray(ks, np.float64)[:, None],
                      inv / inv.sum(1, keepdims=True)]
            best = min(_objective(names, edges, s, *args, jnp.float64)[4]
                       for s in starts)
            e = {"stage_mu": float(np.max(np.abs(p_sm - sm) / sm)),
                 "stage_var": float(np.max(np.abs(p_sv - sv) / (sm * sm))),
                 "makespan_mu": abs(p_m - m) / m,
                 "makespan_var": abs(p_v - v) / (m * m),
                 "simplex": float(max(np.max(np.abs(p_W.sum(1) - 1.0)),
                                      -np.min(p_W))),
                 "descent": obj / best}
            for k, val in e.items():
                val = val if np.isfinite(val) else float("inf")
                num[k] = max(num[k], val)
                if val > chk["limits"][k]:
                    bad.add(i)
            if dtype_name == "float64" and chk.get("mc_trials"):
                st = {n: (family, mus_i[j], sgs_i[j], 0.0)
                      for j, n in enumerate(names)}
                mc = rdag.mc_makespan(names, edges, st, dec.weights,
                                      chk["mc_trials"],
                                      np.random.default_rng([seed, 5, i]))
                notes.append(f"dag solve {i}: makespan mean {p_m:.6g} "
                             f"(reference {m:.6g}, Monte Carlo {mc:.6g} over "
                             f"{chk['mc_trials']} runs)")
    record["notes"].extend(notes)
    return num, bad


def check(record: dict, config: dict, seed: int) -> list:
    num, bad = compare(record, config, seed)
    record["failed"] = len(bad)
    lim = config["check"]["limits"]
    return [{"name": k, "value": v, "limit": lim[k]} for k, v in num.items()]
