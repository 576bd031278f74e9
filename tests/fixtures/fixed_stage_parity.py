"""Solves of small DAGs with single-channel stages, and the fixture of their
decisions that ``tests/test_workflow.py`` holds the solver to.

The fixture was written by the solver of commit e6e99da, which still
carried single-channel stages as PGD rows, with this file's inputs:

    git archive e6e99da src | tar -x -C <dir>
    PYTHONPATH=<dir>/src JAX_PLATFORMS=cpu \\
        python tests/fixtures/fixed_stage_parity.py

Floats are stored as Python floats, which hold float32 values exactly.
"""
import json
import os

import numpy as np

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "fixed_stage_parity.json")
IMPLS = ("xla", "pallas_interpret")
SOLVE = dict(steps=16, restarts=1, num_t=32, presolve_num_t=16,
             eval_num_t=64)


def _stage(name, k, seed, family="normal"):
    from repro.workflow import Stage

    rng = np.random.default_rng(seed)
    mus = rng.uniform(10, 40, k)
    return Stage(name, mus, mus * rng.uniform(0.05, 0.4, k), family=family)


def dag(name):
    """``chain``: 4 -> 1 -> 5 -> 1 channels. ``join``: a single job fans
    out to a 3-wide and a single-channel lognormal stage, which join into
    a 4-wide stage and a last single job. ``wide``: a diamond with no
    single-channel stage."""
    from repro.workflow import StageDAG, linear_edges

    if name == "chain":
        stages = [_stage(n, k, 40 + i)
                  for i, (n, k) in enumerate(zip("abcd", (4, 1, 5, 1)))]
        return StageDAG(stages, linear_edges(list("abcd")))
    if name == "wide":
        stages = [_stage(n, k, 60 + i)
                  for i, (n, k) in enumerate(zip("abcd", (3, 4, 2, 5)))]
        return StageDAG(stages, [("a", "b"), ("a", "c"), ("b", "d"),
                                 ("c", "d")])
    stages = [_stage("a", 1, 50), _stage("b", 3, 51, "lognormal"),
              _stage("c", 1, 52, "lognormal"), _stage("d", 4, 53),
              _stage("e", 1, 54)]
    return StageDAG(stages, [("a", "b"), ("a", "c"), ("b", "d"),
                             ("c", "d"), ("d", "e")])


# (case, DAG, extra solve_dag arguments); ``dirty`` re-solves warm from
# the same DAG's cold decision on the same path
CASES = (("chain", "chain", {}),
         ("join", "join", {"lam_var": 0.5}),
         ("chain_dirty", "chain", {"dirty": ("b", "c")}),
         ("wide", "wide", {"lam_var": 0.5}))


def decision(dec):
    return {"weights": {k: [float(x) for x in v]
                        for k, v in dec.weights.items()},
            "makespan_mu": float(dec.makespan_mu),
            "makespan_var": float(dec.makespan_var),
            "stage_mu": [float(x) for x in dec.stage_mu],
            "stage_var": [float(x) for x in dec.stage_var]}


def solve(case, impl):
    from repro.workflow import solve_dag

    _, name, kw = next(c for c in CASES if c[0] == case)
    g = dag(name)
    kw = dict(SOLVE, impl=impl, **kw)
    if "dirty" in kw:
        kw["warm_start"] = solve_dag(g, **dict(SOLVE, impl=impl)).weights
    return solve_dag(g, **kw)


if __name__ == "__main__":
    out = {case: {impl: decision(solve(case, impl)) for impl in IMPLS}
           for case, _, _ in CASES}
    with open(PATH, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
