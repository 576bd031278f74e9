"""Records of the launches ``solve_dag`` really makes, for the
launch-accounting tests.

The solver books its kernel work from shapes (``workflow.solve._launches``)
while ``kernels/ops.py`` and the kernel decide the layout. The ``launches``
fixture records every call of the dispatch boundary (``ops._moments_fwd`` /
``ops._moments_grads``) as it is traced: its mode, family and rows, the
rows and block it launched with (what ``ops`` padded to, and the block
``lax.map`` walked or the kernel ran), the channel axis, ``num_t`` and the
kernel's lane ``pack`` (1 on the XLA path, which packs nothing). JAX's
caches are cleared per case, so every program of the solve is traced and
recorded once, in the order the ladder runs its rungs; :func:`solve` runs
the three rungs at distinct ``num_t``, so no two programs share a trace.
:func:`check` holds the profile to exactly those launches, rung by rung,
with a PGD loop's launches equal to the steps the loop ran.
"""
import jax
import pytest

from repro.kernels import frontier_grid as fg
from repro.kernels import ops
from repro.workflow import solve_dag
from workflow_shapes import SHAPES, make_dag

PRE_T, NUM_T, EVAL_T = 16, 32, 64
STEPS, PRE_STEPS = 4, 3
KEYS = ("mode", "family", "rows", "rows_padded", "block_f", "k", "num_t",
        "pack")


class _PackSpy:
    """``frontier_grid``'s view of ``kernels.autotune``: passes everything
    through and hands each ``pack_factor`` the kernel takes to ``log``."""

    def __init__(self, at, log):
        self._at, self._log = at, log

    def __getattr__(self, name):
        return getattr(self._at, name)

    def pack_factor(self, block_f, num_k):
        pack = self._at.pack_factor(block_f, num_k)
        self._log(pack)
        return pack


@pytest.fixture
def launches(monkeypatch):
    """Records of every dispatch traced from here on, in order."""
    jax.clear_caches()
    recs = []
    real_fwd, real_grads = ops._moments_fwd, ops._moments_grads
    real_pad, real_blocks = ops._pad_rows, ops._row_blocks
    real_fg, real_fg_grads = fg.frontier_grid, fg.frontier_grid_with_grads

    def open_rec(mode, W, num_t, impl, dist_id):
        F, K = W.shape
        recs.append({"mode": mode, "family": dist_id, "rows": F,
                     "rows_padded": F, "block_f": F, "k": K,
                     "num_t": num_t, "pack": 1 if impl == "xla" else None})

    def fwd(W, mus, sigmas, extra, num_t, impl, bf, z, dist_id):
        open_rec("fwd", W, num_t, impl, dist_id)
        return real_fwd(W, mus, sigmas, extra, num_t, impl, bf, z, dist_id)

    def grads(W, mus, sigmas, extra, num_t, impl, bf, z, dist_id,
              param_grads=False):
        open_rec("pgrad" if param_grads else "grad", W, num_t, impl, dist_id)
        return real_grads(W, mus, sigmas, extra, num_t, impl, bf, z, dist_id,
                          param_grads=param_grads)

    def pad_rows(pad, W, *rest):
        out = real_pad(pad, W, *rest)
        recs[-1]["rows_padded"] = int(out[0].shape[0])
        return out

    def row_blocks(bf, W, *rest):          # the XLA path's lax.map blocks
        recs[-1]["block_f"] = int(bf)
        return real_blocks(bf, W, *rest)

    def kernel(real):
        def launch(W, *args, block_f, **kw):
            recs[-1]["block_f"] = min(block_f, W.shape[0])
            return real(W, *args, block_f=block_f, **kw)
        return launch

    def take_pack(pack):
        recs[-1]["pack"] = int(pack)

    monkeypatch.setattr(ops, "_moments_fwd", fwd)
    monkeypatch.setattr(ops, "_moments_grads", grads)
    monkeypatch.setattr(ops, "_pad_rows", pad_rows)
    monkeypatch.setattr(ops, "_row_blocks", row_blocks)
    monkeypatch.setattr(fg, "frontier_grid", kernel(real_fg))
    monkeypatch.setattr(fg, "frontier_grid_with_grads", kernel(real_fg_grads))
    monkeypatch.setattr(fg, "_at", _PackSpy(fg._at, take_pack))
    yield recs
    jax.clear_caches()


def solve(dag, impl, **kw):
    kw = {"steps": STEPS, "presolve_steps": PRE_STEPS, "restarts": 1,
          "num_t": NUM_T, "presolve_num_t": PRE_T, "eval_num_t": EVAL_T,
          "plateau_patience": None, "impl": impl, **kw}
    return solve_dag(dag, **kw)


def check(dec, recs):
    """The profile's entries are the recorded launches, rung by rung; a
    PGD loop's entries launch once per step it ran, all others once."""
    p = dec.profile
    booked = [{k: e[k] for k in KEYS} for e in p["launches"]]
    assert booked == recs
    ran = {"presolve": p.get("presolve_steps_run"),
           "refine": p.get("refine_steps_run")}
    for e in p["launches"]:
        loop = e["phase"] in ran and e["k"] > 1
        assert e["launches"] == (ran[e["phase"]] if loop else 1), e
        assert e["rows_padded"] % e["block_f"] == 0, e
    return p


def phases(p):
    return [e["phase"] for e in p["launches"]]


def check_xla_case(shape, family, recs):
    """One XLA solve of ``shape`` in ``family``, held to its records; the
    ladder's rungs and families are the ones the DAG calls for."""
    dag = make_dag(shape, family, seed=SHAPES.index(shape))
    p = check(solve(dag, "xla"), recs)
    groups = len({s.dist_id for s in dag.stages})
    if shape == "single":
        # no stage can move: the ladder scores, it never descends
        assert phases(p) == ["triage"] * groups + ["final_score"] * groups
        assert p["presolve_steps_run"] == p["refine_steps_run"] == 0
    else:
        assert p["presolve_steps_run"] == PRE_STEPS
        assert p["refine_steps_run"] == STEPS
        assert set(phases(p)) == {"presolve", "triage", "refine",
                                  "final_score"}
    assert {e["family"] for e in p["launches"]} == {
        s.dist_id for s in dag.stages}
