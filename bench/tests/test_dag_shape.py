"""The Epigenomics configuration builds the workflow its source describes:
per lane a split, four chunked jobs over the lane's chunks and a merge,
then a global merge, the index and the pileup."""
import numpy as np

from bench.systems import dag
from bench.tests import small

SEED = 2**31 + 77


def _built(config=None, seed=SEED):
    return dag.make_dag(config or small.config("epigenomics"), seed)


def test_stages_edges_and_channels():
    cfg = small.config("epigenomics")
    wf = cfg["workflow"]
    names, edges, mus, sigmas = _built(cfg)
    lanes = wf["lanes"]
    assert len(names) == lanes * len(wf["lane"]) + len(wf["tail"]) == 45
    assert len(set(names)) == len(names)
    ks = dict(zip(names, (len(m) for m in mus)))
    assert sorted(dag.lane_chunks(wf)) == [328] * 6 + [329]
    assert sum(ks[f"map.{i}"] for i in range(lanes)) == wf["chunks"]
    for i, c in enumerate(dag.lane_chunks(wf)):
        for job in wf["lane"]:
            assert ks[f"{job}.{i}"] == (c if job in wf["chunked"] else 1)
        chain = [f"{job}.{i}" for job in wf["lane"]]
        assert list(zip(chain, chain[1:])) == \
            [e for e in edges if e[0] in chain and e[1] in chain]
        assert (chain[-1], "mapMerge") in edges
    assert ("mapMerge", "maqIndex") in edges and ("maqIndex", "pileup") in edges
    assert len(edges) == lanes * (len(wf["lane"]) - 1) + lanes + 2


def test_an_even_share_gives_each_chunk_its_job_mean():
    cfg = small.config("epigenomics")
    wf = cfg["workflow"]
    names, _, mus, sigmas = _built(cfg)
    lo, hi = wf["node_factor"]
    for n, m, s in zip(names, mus, sigmas):
        per_chunk = m / len(m) / wf["mean_s"][n.split(".")[0]]
        assert np.all((lo <= per_chunk) & (per_chunk <= hi))
        ratio = s / m
        assert np.all((wf["sigma_ratio"][0] <= ratio)
                      & (ratio <= wf["sigma_ratio"][1]))


def test_the_seed_fixes_the_statistics():
    a, b, c = _built(), _built(), _built(seed=SEED + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a[2], b[2]))
    assert not all(np.array_equal(x, y) for x, y in zip(a[2], c[2]))
