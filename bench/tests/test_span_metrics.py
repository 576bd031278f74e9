"""The readers of the solver's own spans and launch counts: host work and
device waits a solve, and the kernel grid's padding share."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import run as bench_run
from bench.systems import dag as dag_system
from bench.tests import small

SEED = 2**31 + 91


def _read(name, record):
    reader, suffix = bench_run.metric_reader(name)
    assert suffix == "solve"
    return reader.read(record, suffix)


def _span(name, phase, ts, dur):
    return {"type": "span", "name": name, "ts_us": ts, "dur_us": dur,
            "attrs": {"phase": phase}}


def _solve_spans(t0):
    """One solve: phases of 10, 30, 20, 15 and 5 us, waits of 25, 5, 12
    and 3 us inside the last four."""
    out, t = [], t0
    for phase, dur, wait in (("starts", 10, 0), ("presolve", 30, 25),
                             ("triage", 20, 5), ("refine", 15, 12),
                             ("final_score", 5, 3)):
        if wait:
            out.append(_span("solver.wait", phase, t + dur - wait, wait))
        out.append(_span("solver.phase", phase, t, dur))
        t += dur
    return out


def test_host_and_wait_split_the_phases():
    spans = _solve_spans(0) + _solve_spans(100)
    spans.append({"type": "event", "name": "audit.dirty", "ts_us": 1.0,
                  "attrs": {}})
    rec = {"kind": "dag", "solves": 2, "spans": spans}
    assert _read("solver.wait_ms.solve", rec) == pytest.approx(45e-3)
    assert _read("solver.host_ms.solve", rec) == pytest.approx(35e-3)


def test_no_wait_spans_is_none():
    # the phases alone, as a program without wait spans records them
    spans = [r for r in _solve_spans(0) if r["name"] == "solver.phase"]
    rec = {"kind": "dag", "solves": 1, "spans": spans}
    assert _read("solver.host_ms.solve", rec) is None
    assert _read("solver.wait_ms.solve", rec) is None
    for rec in ({"kind": "dag", "solves": 0, "spans": _solve_spans(0)},
                {"kind": "dag", "solves": 3}):
        assert _read("solver.host_ms.solve", rec) is None
        assert _read("solver.wait_ms.solve", rec) is None


def _decision(launches):
    return SimpleNamespace(profile={"launches": launches})


def test_pad_share_weighs_each_launch_by_its_grid():
    a = {"launches": 10, "channels": 30, "rows_padded": 12, "k": 5,
         "num_t": 64}
    b = {"launches": 1, "channels": 30, "rows_padded": 8, "k": 5,
         "num_t": 2048}
    rec = {"kind": "dag", "log": [(None, _decision([a, b])),
                                  (None, _decision([a]))]}
    real = 2 * 10 * 30 * 64 + 30 * 2048
    slots = 2 * 10 * 12 * 5 * 64 + 8 * 5 * 2048
    assert _read("kernel.pad_share.solve", rec) == pytest.approx(
        100 * (1 - real / slots))


def test_pad_share_without_counts_is_none():
    rec = {"kind": "dag", "log": [(None, SimpleNamespace(profile={}))]}
    assert _read("kernel.pad_share.solve", rec) is None
    assert _read("kernel.pad_share.solve", {"kind": "dag"}) is None


def _small_window():
    """Solves of the small Epigenomics configuration, traced, as the
    cell's record holds them."""
    from repro.obs import trace as obs
    from repro.workflow.solve import solve_dag

    cfg = small.epigenomics()
    names, edges, mus, sigmas = dag_system.make_dag(cfg, SEED)
    dag = dag_system._stage_dag(names, edges, mus, sigmas, "normal")
    log = []
    with obs.capture() as spans:
        for _ in range(2):
            log.append((None, solve_dag(dag, **cfg["solve"], block_f=8)))
    rec = {"kind": "dag", "solves": 2, "spans": spans, "log": log}
    return rec, mus, cfg["solve"]["num_t"]


def test_the_program_feeds_the_readers():
    rec, mus, num_t = _small_window()
    host = _read("solver.host_ms.solve", rec)
    wait = _read("solver.wait_ms.solve", rec)
    phases = sum(sum(dec.profile["phase_us"].values())
                 for _, dec in rec["log"]) / 2 / 1e3
    assert host > 0 and wait > 0
    assert host + wait == pytest.approx(phases, rel=1e-3)
    # the hand count: every rung pads each of its rows to the widest
    # stage, and its rows to a multiple of the block of 8
    ks = np.array([len(m) for m in mus])
    real = slots = 0
    for _, dec in rec["log"]:
        p = dec.profile
        surv = p["survivors"]
        for n, steps, T in ((p["starts"], p["presolve_steps_run"],
                             p["presolve_num_t"]),
                            (2 * p["starts"], 1, p["presolve_num_t"]),
                            (surv, p["refine_steps_run"], num_t),
                            (3 * surv, 1, p["eval_num_t"])):
            rows = n * len(ks)
            real += steps * n * ks.sum() * T
            slots += steps * -(-rows // 8) * 8 * ks.max() * T
    assert _read("kernel.pad_share.solve", rec) == pytest.approx(
        100 * (1 - real / slots), abs=0.1)


@pytest.mark.parametrize("attrs", [
    {"phase": "triage"}, {"stage": "commit", "phase": "x"},
    {"mode": "grad", "F": 8}, {"F": 8}])
def test_annotation_label_is_the_harness_label(attrs):
    # the program labels its profiler annotations by the rule the harness
    # uses to name spans: the two must agree for a span to find its own
    from bench import trace_reduce
    from repro.obs import trace as obs

    rec = {"name": "solver.phase", "attrs": attrs}
    assert obs.label(rec["name"], attrs) == trace_reduce.span_label(rec)
