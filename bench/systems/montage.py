"""The system under test for a workflow given as a chain of stages, solved
by ``repro.workflow.solve.solve_dag``: the Pegasus Montage mosaic
(``bench/configs/montage.json``).

The configuration's ``workflow.chain`` lists the stages in the order they
run, each a job and its task count; a stage starts when the one before it
has finished. A stage of k tasks shares its job's work over k channels, so
every channel's mean is k times the job's mean runtime (``mean_s``) times a
node factor, and its standard deviation a share of its mean, both drawn
from the seed. The traffic, the window and the check are those of
``bench/systems/dag.py``: the record this module returns is a ``dag``
record, and :func:`compare` takes that module's numbers as they are, all
but ``descent``.

``descent`` is taken over the wide stages' own objective: the returned
split's objective over the stages of more than one channel, composed as
the chain they form, over that of the better plain split of the same
stages. A single job's split is fixed (its one channel takes all the
work), so the ~880 s of single jobs add the same to every split's
makespan and would hold the whole-run ratio near 1 (0.993-0.997 at the
tests' small size), too close to the 1.0 of a solve that returns its
start for a limit to part them.
"""
from __future__ import annotations

import time

import numpy as np

from bench import traffic
from bench.systems import dag as _dag


def make_dag(config: dict, seed: int):
    """(names, edges, mus, sigmas) from the seed; ``mus[i]`` and
    ``sigmas[i]`` are stage ``i``'s channel statistics (arrays of its own
    task count)."""
    wf = config["workflow"]
    rng = np.random.default_rng([seed, 3])
    names, edges, mus, sigmas = [], [], [], []
    for stage in wf["chain"]:
        k = int(stage["tasks"])
        if names:
            edges.append((names[-1], stage["stage"]))
        names.append(stage["stage"])
        mu = k * wf["mean_s"][stage["job"]] * rng.uniform(*wf["node_factor"],
                                                          k)
        mus.append(mu)
        sigmas.append(mu * rng.uniform(*wf["sigma_ratio"], k))
    return names, edges, mus, sigmas


def run(config: dict, mix: dict, seed: int, window) -> dict:
    from repro.workflow.solve import solve_dag

    names, edges, mus0, sg0 = make_dag(config, seed)
    family = config["workflow"]["family"]
    S = len(names)

    def request(rq):
        dag = _dag._stage_dag(names, edges, _dag._scaled(mus0, rq.factors),
                              _dag._scaled(sg0, rq.factors), family)
        return rq.factors, solve_dag(dag, **config["solve"])

    warm_gen = traffic.closed_loop(mix, np.random.default_rng([seed, 1]), S)
    for i in range(mix["warm_requests"] + _dag.MORE_WARM):
        before = len(window.compile_times)
        request(next(warm_gen))
        if i >= mix["warm_requests"] and len(window.compile_times) == before:
            break

    gen = traffic.closed_loop(mix, np.random.default_rng([seed, 2]), S)
    log, ends = [], [window.open()]
    while True:
        rq = next(gen)
        with window.annotate("bench.solve"):
            log.append(request(rq))
        ends.append(time.perf_counter())
        if window.step():
            break
    packs = sorted({(e["phase"], e["pack"])
                    for e in log[0][1].profile["launches"] if "pack" in e})
    return {
        "kind": "dag",
        "attempted": len(log),
        "solves": len(log),
        "log": log,
        "dag": (names, edges, mus0, sg0, family),
        "phase_us": [dec.profile.get("phase_us", {}) for _, dec in log],
        "solve_times_ms": (np.diff(ends) * 1e3).tolist(),
        "ladder_t": _dag.ladder_t(log[0][1].profile,
                                  config["solve"]["num_t"]),
        "notes": [f"montage: {len(log)} solves in the window, methods "
                  f"{sorted({d.method for _, d in log})}, survivors "
                  f"{sorted({d.profile.get('survivors') for _, d in log})}, "
                  f"lane slots a row by rung {packs or 'not counted'}",
                  _dag.pace(ends)],
    }


def _picked(record: dict, config: dict, seed: int) -> list:
    """The solves ``dag.compare`` samples, drawn the same way."""
    log = record["log"]
    rng = np.random.default_rng([seed, 4])
    return sorted(rng.choice(len(log), min(len(log), config["check"]["solves"]),
                             replace=False).tolist())


def wide_descent(record: dict, config: dict, seed: int):
    """``descent`` over the wide stages of the sampled solves: (largest
    ratio, indices of the solves over the limit)."""
    import jax
    import jax.numpy as jnp

    from bench.reference import float64

    names, _, mus0, sg0, family = record["dag"]
    wide = [i for i, m in enumerate(mus0) if len(m) > 1]
    names_w = [names[i] for i in wide]
    edges_w = list(zip(names_w, names_w[1:]))
    ks = np.asarray([len(mus0[i]) for i in wide], np.float64)
    K = int(ks.max())
    mask = _dag._padded([np.ones(int(k)) for k in ks], K) > 0
    lam_var = float(config["solve"].get("lam_var", 0.0))
    nt = config["check"]["eval_num_t"]
    limit = config["check"]["limits"]["descent"]
    worst, bad = 0.0, set()
    with float64(), jax.default_device(jax.devices("cpu")[0]):
        for i in _picked(record, config, seed):
            f, dec = record["log"][i]
            mus = _dag._padded([mus0[j] * f[j] for j in wide], K)
            sgs = _dag._padded([sg0[j] * f[j] for j in wide], K)
            args = (mus, sgs, mask, family, nt, lam_var, jnp.float64)
            W = _dag._padded([np.asarray(dec.weights[n], np.float64)
                              for n in names_w], K)
            inv = np.where(mask, 1.0 / np.where(mask, mus, 1.0), 0.0)
            plain = [mask / ks[:, None], inv / inv.sum(1, keepdims=True)]
            obj = _dag._objective(names_w, edges_w, W, *args)[4]
            best = min(_dag._objective(names_w, edges_w, w, *args)[4]
                       for w in plain)
            ratio = obj / best if np.isfinite(obj / best) else float("inf")
            worst = max(worst, ratio)
            if ratio > limit:
                bad.add(i)
    return worst, bad


def compare(record: dict, config: dict, seed: int,
            dtype_name: str = "float64"):
    """``dag.compare``'s numbers, with ``descent`` from
    :func:`wide_descent`. The control's ``descent`` is the program's, as
    in ``dag.compare``: it is a property of the returned split."""
    chk = config["check"]
    loose = dict(chk, limits=dict(chk["limits"], descent=float("inf")))
    num, bad = _dag.compare(record, dict(config, check=loose), seed,
                            dtype_name)
    num["descent"], bad_w = wide_descent(record, config, seed)
    return num, bad | bad_w


def check(record: dict, config: dict, seed: int) -> list:
    num, bad = compare(record, config, seed)
    record["failed"] = len(bad)
    lim = config["check"]["limits"]
    return [{"name": k, "value": v, "limit": lim[k]} for k, v in num.items()]
