"""Small stage DAGs in every completion-time family, shared by the
launch-accounting and solver-family tests.

Four shapes, each a few stages of at most 16 channels (few distinct
widths, so a solver compiles few programs per family):

* ``lane``: an Epigenomics-like lane, wide stages between single jobs;
* ``diamond``: a fork-join whose branches carry the case's family and whose
  ends stay normal (two family groups unless the family is normal);
* ``wide``: every stage wide, up to 16 channels, forking to two sinks;
* ``single``: every stage a single channel (no PGD row at all).
"""
import numpy as np

from repro.core.distributions import Defective, Drift, Empirical
from repro.workflow import Stage, StageDAG, linear_edges

FAMILIES = ("normal", "lognormal", "drift", "empirical", "defective")

# (stage name, channels, family: "x" the case's, "n" normal), then edges
_SHAPES = {
    "lane": ([("split", 1, "x"), ("filter", 6, "x"), ("sol2sanger", 6, "x"),
              ("map", 6, "x"), ("merge", 1, "x"), ("pileup", 1, "x")],
             linear_edges(["split", "filter", "sol2sanger", "map", "merge",
                           "pileup"])),
    "diamond": ([("a", 4, "n"), ("b", 6, "x"), ("c", 6, "x"), ("d", 4, "n")],
                [("a", "b"), ("a", "c"), ("b", "d"), ("c", "d")]),
    "wide": ([("src", 16, "x"), ("left", 6, "x"), ("right", 16, "x")],
             [("src", "left"), ("src", "right")]),
    "single": ([("a", 1, "x"), ("b", 1, "x"), ("c", 1, "x"), ("d", 1, "x")],
               [("a", "b"), ("a", "c"), ("b", "d"), ("c", "d")]),
}
SHAPES = tuple(_SHAPES)


def family_spec(dist_id, mus, sigmas, rng):
    """A spec of ``dist_id`` for channels ``(mus, sigmas)``, with
    per-channel parameters where the family has them."""
    k = len(mus)
    if dist_id == "drift":
        return Drift(rng.uniform(0.1, 0.6, k).astype(np.float32))
    if dist_id == "defective":
        return Defective(rng.uniform(0.05, 0.3, k).astype(np.float32))
    if dist_id == "empirical":
        return Empirical.from_samples(
            rng.normal(mus[None, :], sigmas[None, :], size=(256, k)))
    return dist_id


def make_dag(shape: str, dist_id: str, seed: int = 0) -> StageDAG:
    stages, edges = _SHAPES[shape]
    rng = np.random.default_rng(seed)
    out = []
    for name, k, role in stages:
        mus = rng.uniform(10, 40, k)
        sigmas = mus * rng.uniform(0.05, 0.4, k)
        fam = family_spec(dist_id if role == "x" else "normal", mus, sigmas,
                          rng)
        out.append(Stage(name, mus, sigmas, family=fam))
    return StageDAG(out, edges)
