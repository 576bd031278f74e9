"""Smoke run of the scheduler's main path on one TPU chip.

    python chip_smoke.py

Everything runs in this one process, in four phases; any failure ends the
run with a non-zero exit code.

1. device — the default device must be a TPU; on any other platform the
   script stops before doing any work.
2. engine — ``WorkflowEngine`` with ``impl="pallas"`` (compiled Mosaic
   kernels) at the full scale of ``benchmarks/serve_trace.py``: its three
   templates, 320 live slots, 400 requests queued before the first tick,
   T=128, calm Poisson arrivals of 24 per tick, 24 ticks.
3. parity — the row set of the engine's busiest tick, solved once through
   ``impl="pallas"`` and once through ``impl="xla"``: per-row mu and var
   agree to 1e-3 relative, the stepped splits to 1e-3 of the simplex.
4. fleet — one launch per mode at the ``cluster_scale`` kernel tick,
   K=1024 channels x F=4096 candidates x T=256 grid points: fwd and grad
   for normal, lognormal and drift, pgrad for normal, each with the block
   the autotune model picks, against ``impl="xla"`` on the same inputs.

Each phase prints one JSON line: compile seconds (JAX's backend-compile
events; cache hits of the persistent compilation cache skip them) apart
from the seconds of warm calls, timed around ``block_until_ready``. The
last line is ``{"ok": true, "device": {...}}``.
"""
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

IMPL = "pallas"           # compiled kernels: never interpret mode, never ref
SEED = 0
TICKS = 24
PARITY_RTOL = 1e-3       # the kernel-vs-oracle tolerance of the test suite
FLEET = dict(F=4096, K=1024, T=256)
FLEET_CASES = [("fwd", "normal"), ("fwd", "lognormal"), ("fwd", "drift"),
               ("grad", "normal"), ("grad", "lognormal"), ("grad", "drift"),
               ("pgrad", "normal")]

_COMPILE_S = [0.0]


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def _report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


def _compile_listener(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


def _rel_err(a, b) -> float:
    """Largest |a - b| relative to |b| elementwise."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def _scaled_err(a, b) -> float:
    """Largest |a - b| relative to the largest |b| of the array."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def engine_phase():
    from benchmarks import serve_trace as st
    from repro.kernels import autotune
    from repro.serve.engine import WorkflowEngine
    from repro.workflow.solve import stack_rows

    templates = st._templates()
    eng = WorkflowEngine(templates, max_live=st.MAX_LIVE, lam_var=0.02,
                         slo_gain=0.5, settle_steps=4, dirty_tol=0.08,
                         num_t=st.NUM_T, seed=SEED, prior_obs=4,
                         impl=IMPL)
    rng = np.random.default_rng(SEED)
    names = list(templates)
    est = {n: st._naive_makespan(d) for n, d in templates.items()}

    def request():
        tpl = names[int(rng.integers(len(names)))]
        if rng.random() < 0.5:
            return (tpl, est[tpl] * float(rng.uniform(0.8, 2.5)))
        return tpl

    for _ in range(st.PREFILL):
        req = request()
        eng.submit(*req) if isinstance(req, tuple) else eng.submit(req)

    compile_s, steady, live_max, buckets, busiest = 0.0, [], 0, set(), None
    for _ in range(TICKS):
        arrivals = [request() for _ in range(int(rng.poisson(st.LAM_CALM)))]
        c0, t0 = _COMPILE_S[0], time.perf_counter()
        out = eng.tick(arrivals)   # launches return numpy: the tick blocks
        dt, dc = time.perf_counter() - t0, _COMPILE_S[0] - c0
        compile_s += dc
        if dc == 0.0:
            steady.append(dt)
        live_max = max(live_max, out["live"])
        rows = eng.last_rows
        for r in rows:
            _check(r.mu is not None and np.isfinite(r.mu) and r.mu > 0
                   and np.isfinite(r.var) and r.var >= 0,
                   f"non-finite moments on row {r.iid}/{r.stage}")
        if rows:
            groups, _, _ = stack_rows(
                [(r.mus, r.sigmas, r.family) for r in rows], kmax=eng.kmax)
            buckets.update(autotune.bucket_rows(len(g.idx)) for g in groups)
        if rows and (busiest is None or len(rows) > len(busiest)):
            busiest = list(rows)
    _check(eng.tick_count >= 20, "fewer than 20 ticks")
    _check(live_max == st.MAX_LIVE, f"live set peaked at {live_max}, "
           f"not {st.MAX_LIVE}")
    _check(len(buckets) >= 2, f"only row buckets {sorted(buckets)} hit")
    tel = eng.telemetry.summary()
    _report("engine", impl=IMPL, ticks=eng.tick_count,
            live_max=live_max, buckets=sorted(buckets),
            launches=tel["counters"]["launches"],
            rows_per_launch=tel["rows_per_launch"], compile_s=compile_s,
            steady_tick_s_median=float(np.median(steady)) if steady else None,
            steady_ticks=len(steady))
    return eng, busiest


def parity_phase(eng, rows):
    from benchmarks import serve_trace as st

    outs = {}
    for impl in (IMPL, "xla"):
        c0, t0 = _COMPILE_S[0], time.perf_counter()
        outs[impl] = st._launch_rows(rows, eng.kmax, st.NUM_T, impl)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        st._launch_rows(rows, eng.kmax, st.NUM_T, impl)
        _report("parity_launch", impl=impl, rows=len(rows),
                compile_s=_COMPILE_S[0] - c0, first_s=first,
                steady_s=time.perf_counter() - t0)
    err = {"mu": 0.0, "var": 0.0, "w_next": 0.0}
    for (idx, m, v, w), (idx_x, mx, vx, wx) in zip(outs[IMPL], outs["xla"]):
        _check(list(idx) == list(idx_x), "family groups differ")
        err["mu"] = max(err["mu"], _rel_err(m, mx))
        err["var"] = max(err["var"], _rel_err(v, vx))
        # splits live on the simplex: their scale is its unit mass
        err["w_next"] = max(err["w_next"],
                            float(np.max(np.abs(np.asarray(w) - wx))))
    _report("parity", rows=len(rows), groups=len(outs[IMPL]),
            max_err=err)
    for k, e in err.items():
        _check(e <= PARITY_RTOL, f"pallas vs xla {k} differ by {e:.3e}")


def _fleet_inputs(family_name: str, rng):
    from repro.core.distributions import Drift

    F, K = FLEET["F"], FLEET["K"]
    e = rng.exponential(size=(F, K))
    W = (e / e.sum(1, keepdims=True)).astype(np.float32)
    mus = rng.uniform(10, 40, K).astype(np.float32)
    sgs = (mus * rng.uniform(0.02, 0.3, K)).astype(np.float32)
    family = (Drift(rng.uniform(0.0, 0.5, K).astype(np.float32))
              if family_name == "drift" else family_name)
    return W, mus, sgs, family


def fleet_phase():
    import jax

    from repro.kernels import autotune, ops

    rng = np.random.default_rng(SEED)
    F, K, T = FLEET["F"], FLEET["K"], FLEET["T"]
    for mode, fam in FLEET_CASES:
        W, mus, sgs, family = _fleet_inputs(fam, rng)

        def launch(impl):
            if mode == "fwd":
                return ops.frontier_moments(W, mus, sgs, num_t=T, impl=impl,
                                            family=family)
            return ops.frontier_moments_with_grads(
                W, mus, sgs, num_t=T, impl=impl, family=family,
                param_grads=mode == "pgrad")

        if (mode, fam) == FLEET_CASES[0] and IMPL == "pallas":
            # the compiled path is a Mosaic kernel; the XLA path is not one
            for impl in (IMPL, "xla"):
                text = jax.jit(lambda W, m, s, impl=impl: ops.frontier_moments(
                    W, m, s, num_t=T, impl=impl, family=family)).lower(
                        W, mus, sgs).compile().as_text()
                _check(("tpu_custom_call" in text) == (impl == IMPL),
                       f"impl={impl!r} compiled to the wrong program")
        timing = {}
        outs = {}
        for impl in (IMPL, "xla"):
            c0, t0 = _COMPILE_S[0], time.perf_counter()
            outs[impl] = jax.block_until_ready(launch(impl))
            first = time.perf_counter() - t0
            t0 = time.perf_counter()
            jax.block_until_ready(launch(impl))
            timing[impl] = dict(compile_s=_COMPILE_S[0] - c0, first_s=first,
                                steady_s=time.perf_counter() - t0)
        bf = autotune.pick_block_f(F, K, T, "pallas", mode != "fwd",
                                   dist_id=ops._resolve_family(family, K)[0],
                                   params=mode == "pgrad")
        names = ["mu", "var"] + [f"d{i}" for i in range(len(outs["xla"]) - 2)]
        err = {}
        for name, a, b in zip(names, outs[IMPL], outs["xla"]):
            a = np.asarray(a)
            _check(np.all(np.isfinite(a)), f"{mode}/{fam} {name} not finite")
            err[name] = (_rel_err(a, b) if name in ("mu", "var")
                         else _scaled_err(a, b))
        _report("fleet", mode=mode, family=fam, F=F, K=K, T=T, block_f=bf,
                max_err=err, **{f"{impl}_{k}": v for impl, t in timing.items()
                                for k, v in t.items()})
        for name, e in err.items():
            _check(e <= PARITY_RTOL,
                   f"{mode}/{fam} pallas vs xla {name} differ by {e:.3e}")


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (the default device is "
              f"{dev.platform!r}); nothing was run", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_compile_listener)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    _report("device", cache_dir=cache, **device)
    t0 = time.perf_counter()
    eng, rows = engine_phase()
    parity_phase(eng, rows)
    fleet_phase()
    _report("total", wall_s=time.perf_counter() - t0,
            compile_s=_COMPILE_S[0])
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
