"""block_f autotuning for the frontier kernels.

A program's working set is a (T, block_f) survival tile plus (K, block_f)
channel tiles; the fused moments+gradient kernel holds several times that
(per-channel accumulators and (K, block_f) gradient outputs live in the same
VMEM), so the right block size depends on (K, num_t, backend, mode) —
too big overflows VMEM on TPU (or blows the per-block peak-memory budget of
the chunked XLA path on CPU), too small wastes launches on grid overhead.

Three layers, cheapest first:

1. A VMEM/working-set **budget model** (:func:`pick_block_f`) — pure
   arithmetic, used whenever ``ops.frontier_moments`` is called without an
   explicit ``block_f``. Deterministic per shape, safe to consult at trace
   time inside jit.
2. An **in-process cache** keyed by ``(device, backend, F, K, num_t, mode,
   dist_id)`` so the model (or a sweep result) is computed once per process.
3. A **timed sweep** (:func:`sweep`) over ``block_f in {32..512}`` x the
   requested ``num_t`` that benchmarks the real kernel on synthetic data and
   persists the winner to ``experiments/bench/autotune_cache.json`` — run by
   ``benchmarks/cluster_scale.py`` (and ``scripts/bench_smoke.sh``) so tuned
   configs survive across processes and ride along in the repo.

The completion-time family is part of the key AND the model: the fused
adjoint carries two per-channel accumulator pairs for the ``drift`` family
(vs one for the scale-like families), and the ``empirical`` mixture streams
3C extra CDF tiles per channel — different working sets, different safe
block sizes. So is the launch *mode*: ``fwd`` (forward moments only),
``grad`` (fused W-adjoints — the PGD tick) and ``pgrad`` (full-parameter
adjoints for the estimation loop: up to six accumulator pairs plus six more
(K, block_f) output tiles, the largest working set of the three). So is the
device: a sweep timed on one device kind never chooses another's launch
(``device_tag``). Cache keys are versioned (``v4:``); older keys migrate on
load — v2 ``fused0/fused1`` map to ``fwd``/``grad`` (``pgrad`` shapes never
existed before v3), un-versioned keys additionally pick up the normal
family, and every pre-v4 key was timed on the CPU backend, so it migrates
to the ``cpu`` device — so an existing JSON cache survives every schema
bump without steering an accelerator.
"""
from __future__ import annotations

import json
import logging
import os
import re
import threading
import time
from typing import Dict, Optional, Sequence, Tuple

_log = logging.getLogger(__name__)

__all__ = ["BLOCK_F_CANDIDATES", "ROW_BUCKETS", "vmem_bytes", "pick_block_f",
           "pack_factor",
           "device_tag",
           "bucket_rows", "lookup", "sweep", "clear_cache",
           "default_cache_path", "cache_state", "load_cache_state"]

BLOCK_F_CANDIDATES: Tuple[int, ...] = (32, 64, 128, 256, 512)

# serving row-count buckets: the continuous-batching engine pads its stacked
# row axis UP to one of these before the launch (see bucket_rows)
ROW_BUCKETS: Tuple[int, ...] = (8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                                4096)

# v5e has 128 MiB of VMEM per core (its compiler refuses a larger allocation).
# The Pallas launches pass this budget as their scoped-VMEM limit, and
# vmem_bytes models every buffer of a program against it — double-buffered
# blocks included, which is more than the limit itself counts — so a block
# the model accepts compiles, with the rest of VMEM to spare.
_VMEM_BUDGET_BYTES = 48 * 1024 * 1024
# the XLA path is bounded by host/device peak memory per lax.map block, not
# VMEM — a much looser working-set ceiling (the (bf, T, K) intermediates)
_XLA_BLOCK_BUDGET_BYTES = 1024 * 1024 * 1024

_KEY_VERSION = "v4"  # v4: device-aware keys; v3: mode-aware keys

_CACHE: Dict[str, dict] = {}
_JSON_LOADED: set = set()


def default_cache_path() -> str:
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    return os.path.join(root, "experiments", "bench", "autotune_cache.json")


def _mode(fused: bool, params: bool) -> str:
    if not fused:
        return "fwd"
    return "pgrad" if params else "grad"


def device_tag() -> str:
    """The default device's kind as a key component (``cpu``,
    ``tpu_v5_lite``): launch shapes are timed and chosen per device kind."""
    import jax
    return jax.devices()[0].device_kind.lower().replace(" ", "_")


def _key(F: int, K: int, num_t: int, backend: str, fused: bool,
         dist_id: str = "normal", params: bool = False,
         stacked: bool = False) -> str:
    # the stacked (per-row statistics) layout streams 2+E more (K, bf)
    # input tiles from HBM per program; its suffix is additive
    suffix = ":stk" if stacked else ""
    return (f"{_KEY_VERSION}:{device_tag()}:{backend}:F{F}:K{K}"
            f":T{num_t}:mode{_mode(fused, params)}:fam{dist_id}{suffix}")


_V2_RE = re.compile(r"^v2:(?P<body>.*):fused(?P<fused>[01]):fam(?P<fam>\w+)$")
_LEGACY_RE = re.compile(r"^(?P<body>[^:]+:F\d+:K\d+:T\d+):fused(?P<fused>[01])$")


def _migrate_key(k: str) -> str:
    """Lift a v3, v2 (fused-flag) or legacy (pre-family, un-versioned) key
    to v4.

    v2 ``fused0``/``fused1`` become ``modefwd``/``modegrad`` (the pgrad mode
    is new in v3, so no v2 entry can alias it); un-versioned legacy keys are
    additionally normal-family. Keys older than v4 name no device: all of
    them were timed on the CPU backend, so they become ``cpu`` entries.
    """
    if k.startswith(f"{_KEY_VERSION}:"):
        return k
    m = _V2_RE.match(k)
    if m:
        mode = "grad" if m.group("fused") == "1" else "fwd"
        k = f"v3:{m.group('body')}:mode{mode}:fam{m.group('fam')}"
    m = _LEGACY_RE.match(k)
    if m:
        mode = "grad" if m.group("fused") == "1" else "fwd"
        k = f"v3:{m.group('body')}:mode{mode}:famnormal"
    if k.startswith("v3:"):
        return f"{_KEY_VERSION}:cpu:{k[len('v3:'):]}"
    return k  # unknown schema: keep verbatim (never collides with v4 keys)


def _grad_acc_pairs(dist_id: str, params: bool = False) -> int:
    # local import: distributions sits above kernels in the package DAG but
    # this module must stay importable before repro.core finishes init
    from repro.core.distributions import family_features
    use_1, use_t, use_z = family_features(dist_id, params=params)
    return int(use_1) + int(use_t) + int(use_z)


def _mix_tiles(dist_id: str) -> int:
    from repro.core.distributions import EMP_COMPONENTS
    # transient per-component z/cdf tiles the mixture family keeps live
    return EMP_COMPONENTS - 1 if dist_id == "empirical" else 0


LANES = 128     # f32 lanes per vreg
SUBLANES = 8    # f32 sublanes per vreg: the kernels' channel-chunk height


def _vmem_tile(rows: int, cols: int) -> int:
    """Bytes of one f32 VMEM buffer: (8, 128) tiles, padded on both axes."""
    return (4 * (-(-rows // SUBLANES) * SUBLANES)
            * (-(-cols // LANES) * LANES))


def pack_factor(block_f: int, num_k: int) -> int:
    """Lane slots per row of a ``frontier_grid`` program of ``block_f`` rows
    and ``num_k`` channels: the kernel's ``pack``, which each launch
    takes from its own shapes.

    A block fills 128 * ceil(block_f / 128) lanes whatever block_f is; the
    largest c with block_f * c inside those lanes and ceil(K / c) >= 8 (one
    sublane chunk per slot) deals each row's channels over c slots, so a
    program walks ceil(K / c) channels instead of K. 1 where the block
    leaves no lanes for a second slot or K is too short to split.
    """
    lanes = LANES * -(-block_f // LANES)
    c = lanes // block_f
    while c > 1 and -(-num_k // c) < SUBLANES:
        c -= 1
    return c


def vmem_bytes(block_f: int, num_k: int, num_t: int, fused: bool = False,
               dist_id: str = "normal", params: bool = False) -> int:
    """VMEM one kernel program allocates, in bytes (f32), in the
    channel-major layout of ``frontier_grid``, packed by
    :func:`pack_factor` as the kernel launches it: every (K, bf) tile below is
    (ceil(K / c), c * bf) for c slots a row.

    * Blocks, each double-buffered by the grid pipeline: the (K, bf) tiles
      of W, mus, sigmas and the E extra rows (shared statistics arrive
      lane-broadcast to the same (K, bf) tile as per-row ones); the two
      (1, bf) moment rows; and in fused modes the (K, bf) gradient outputs
      — two, or eight with ``params`` (the ``pgrad`` key mode).
    * Scratch of the fused modes: the (K, bf) reach rows and one (K, bf)
      accumulator pair per live feature — two pairs for ``drift``, up to
      three in full-parameter mode (the z feature of ``lognormal`` and
      ``defective``; defective's {1, t, z} basis is the widest of any
      family).
    * Work tiles: the (T, bf) values live in a K-loop step (time grid, log
      joint CDF, z-scores, the family CDF), more in the fused passes, plus
      the ``empirical`` mixture's per-component tiles.

    * The combine across a packed row's slots: two more (T, bf) tiles.

    Lanes pad to 128, so an unpacked block_f below 128 costs what 128 does.
    The compiler's scoped-VMEM check counts less than this (not every
    block), so a program this model fits within ``_VMEM_BUDGET_BYTES``
    compiles under that limit.
    """
    from repro.core.distributions import extra_rows
    c = pack_factor(block_f, num_k)
    lanes = c * block_f
    chan = _vmem_tile(-(-num_k // c), lanes)
    blocks = (3 + extra_rows(dist_id)) * chan + 2 * _vmem_tile(1, lanes)
    scratch = 0
    if fused:
        blocks += (8 if params else 2) * chan
        scratch = (1 + 2 * _grad_acc_pairs(dist_id, params)) * chan
    work = ((12 if fused else 8) + 2 * _mix_tiles(dist_id)
            + (2 if c > 1 else 0))
    return 2 * blocks + scratch + work * _vmem_tile(num_t, lanes)


def _xla_block_bytes(block_f: int, num_k: int, num_t: int, fused: bool,
                     dist_id: str = "normal", params: bool = False) -> int:
    # the pure-jnp path materializes (bf, T, K) zscore/cdf/phi intermediates;
    # the mixture family adds per-component copies of them, the z-feature
    # accumulators of full-parameter mode one more. The stacked layout's
    # extra stat rows are (bf, K) — noise against these and not modeled.
    live = (5 if fused else 3) + _mix_tiles(dist_id) + (1 if params else 0)
    return 4 * block_f * num_t * num_k * live


def _fits(block_f: int, K: int, num_t: int, backend: str, fused: bool,
          dist_id: str = "normal", params: bool = False,
          F: Optional[int] = None) -> bool:
    """Whether a candidate block fits the backend's budget. A Pallas
    program of a candidate above ``F`` rows holds F rows, packed as F."""
    if backend == "xla":
        return (_xla_block_bytes(block_f, K, num_t, fused, dist_id, params)
                <= _XLA_BLOCK_BUDGET_BYTES)
    if F is not None:
        block_f = max(min(block_f, F), 1)
    return (vmem_bytes(block_f, K, num_t, fused, dist_id, params)
            <= _VMEM_BUDGET_BYTES)


def pick_block_f(F: int, K: int, num_t: int, backend: str = "xla",
                 fused: bool = False,
                 candidates: Sequence[int] = BLOCK_F_CANDIDATES,
                 dist_id: str = "normal", params: bool = False) -> int:
    """Largest candidate block_f that fits the backend's budget model."""
    feasible = [bf for bf in candidates
                if _fits(bf, K, num_t, backend, fused, dist_id, params, F)]
    pick = max(feasible) if feasible else min(candidates)
    return max(min(pick, F), 1)


def bucket_rows(F: int, buckets: Sequence[int] = ROW_BUCKETS) -> int:
    """Round a stacked row count UP to the next serving working-set bucket.

    A continuous-batching tick stacks a fluctuating number of
    (instance, stage) rows per family launch; keying the ``:stk`` cache —
    and the jit cache above it — at the raw count would re-key (and
    recompile) nearly every tick as instances admit and retire. Callers pad
    the row axis to the bucket by repeating a real row and slice the pad
    rows off after the launch, so every family x fidelity keeps at most one
    compiled program per bucket. Counts past the last bucket pass through
    unchanged (that scale should be sharded, not padded further).
    """
    F = int(F)
    for b in buckets:
        if F <= b:
            return int(b)
    return F


def _load_json(cache_path: str) -> None:
    if cache_path in _JSON_LOADED:
        return
    _JSON_LOADED.add(cache_path)
    try:
        with open(cache_path) as f:
            disk = json.load(f)
    except (OSError, ValueError):
        return
    for k, v in disk.items():
        k = _migrate_key(k)
        # sweep results on disk outrank anything model-derived in-process
        if k not in _CACHE or _CACHE[k].get("source") != "sweep":
            _CACHE[k] = v


# per-thread record of how the most recent lookup resolved, read by the
# kernel-launch span emitters (repro.obs) — a return-channel attribute, so
# lookup's signature and call sites stay unchanged
_LOOKUP_LOCAL = threading.local()


def last_outcome() -> str:
    """``"hit"`` | ``"miss"`` for this thread's latest :func:`lookup`."""
    return getattr(_LOOKUP_LOCAL, "outcome", "none")


def lookup(F: int, K: int, num_t: int, backend: str = "xla",
           fused: bool = False, cache_path: Optional[str] = None,
           dist_id: str = "normal", params: bool = False,
           stacked: bool = False) -> int:
    """block_f for a launch shape: in-process cache -> JSON cache -> model.

    This is what ``ops.frontier_moments`` consults when ``block_f`` is not
    explicitly passed. Never runs a timed sweep itself (deterministic and
    trace-safe); :func:`sweep` feeds better-than-model entries into the same
    caches. ``params`` selects the full-parameter-adjoint (``pgrad``) launch
    mode the estimation loop's custom VJP uses; ``stacked`` the per-row
    statistics layout (its own key suffix: the two layouts read different
    amounts of HBM, so a sweep of one does not time the other).
    """
    _load_json(cache_path or default_cache_path())
    key = _key(F, K, num_t, backend, fused, dist_id, params, stacked)
    hit = _CACHE.get(key)
    if hit is not None:
        _LOOKUP_LOCAL.outcome = "hit"
        return max(min(int(hit["block_f"]), F), 1)
    _LOOKUP_LOCAL.outcome = "miss"
    bf = pick_block_f(F, K, num_t, backend, fused, dist_id=dist_id,
                      params=params)
    _log.debug(
        "autotune cache miss: F=%d K=%d num_t=%d backend=%s dist_id=%s "
        "mode=%s stacked=%s -> model block_f=%d (run autotune.sweep to "
        "replace the model pick with a timed one)",
        F, K, num_t, backend, dist_id, _mode(fused, params), stacked, bf)
    _CACHE[key] = {"block_f": bf, "source": "model"}
    return bf


def sweep(F: int, K: int, num_t: int, backend: str = "xla",
          fused: bool = False, repeats: int = 2, seed: int = 0,
          candidates: Sequence[int] = BLOCK_F_CANDIDATES,
          cache_path: Optional[str] = None, dist_id: str = "normal",
          params: bool = False) -> dict:
    """Time the real kernel across feasible block_f values; cache the winner.

    Returns the winning entry ``{"block_f", "source": "sweep", "us", "timings"}``
    and persists it (in-process + JSON) under
    ``(F, K, num_t, backend, fused, dist_id, params)``.
    """
    import jax
    import numpy as np

    from repro.core.distributions import Drift, extra_rows
    from . import ops

    rng = np.random.default_rng(seed)
    e = rng.exponential(size=(F, K))
    W = (e / e.sum(1, keepdims=True)).astype(np.float32)
    mus = rng.uniform(10, 40, K).astype(np.float32)
    sgs = (mus * rng.uniform(0.02, 0.3, K)).astype(np.float32)
    if dist_id == "drift":
        family = Drift(rng.uniform(0.0, 0.5, K).astype(np.float32))
    elif dist_id == "empirical":
        from repro.core.distributions import Empirical
        family = Empirical.from_samples(
            rng.normal(mus[None, :], sgs[None, :], size=(256, K)))
    elif dist_id == "defective":
        from repro.core.distributions import Defective
        family = Defective(rng.uniform(0.0, 0.3, K).astype(np.float32))
    else:
        family = dist_id

    feasible = [bf for bf in candidates
                if _fits(bf, K, num_t, backend, fused, dist_id, params, F)]
    if not feasible:
        feasible = [min(candidates)]
    timings = {}
    for bf in feasible:
        def run(bf=bf):
            if fused:
                out = ops.frontier_moments_with_grads(
                    W, mus, sgs, num_t=num_t, impl=backend, block_f=bf,
                    family=family, param_grads=params)
            else:
                out = ops.frontier_moments(
                    W, mus, sgs, num_t=num_t, impl=backend, block_f=bf,
                    family=family)
            jax.block_until_ready(out)
        run()  # compile + warm
        samples = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            run()
            samples.append((time.perf_counter() - t0) * 1e6)
        timings[bf] = sorted(samples)[len(samples) // 2]
    best_bf = min(timings, key=timings.get)
    entry = {"block_f": int(best_bf), "source": "sweep",
             "us": float(timings[best_bf]),
             "timings": {str(k): float(v) for k, v in timings.items()}}
    key = _key(F, K, num_t, backend, fused, dist_id, params)
    _CACHE[key] = entry
    path = cache_path or default_cache_path()
    disk = {}
    try:
        with open(path) as f:
            # normalize older keys on rewrite so the file converges to v4
            disk = {_migrate_key(k): v for k, v in json.load(f).items()}
    except (OSError, ValueError):
        pass
    disk[key] = entry
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(disk, f, indent=1, sort_keys=True)
    return entry


def clear_cache() -> None:
    """Drop the in-process cache (tests use this to exercise JSON round-trips)."""
    _CACHE.clear()
    _JSON_LOADED.clear()


def cache_state() -> dict:
    """Snapshot the in-process cache for a pipeline checkpoint manifest.

    The kill/restore tick-parity contract (see ``ckpt.store``) includes the
    autotune cache: a restored replica that re-derives block_f from the model
    while the original process held a sweep result would launch a different
    kernel shape — numerically identical, but a different compile and a
    different performance cliff. Snapshotting the cache (entries are small
    JSON-able dicts) makes the restored process pick identical launches.
    """
    return {k: dict(v) for k, v in _CACHE.items()}


def load_cache_state(state: dict) -> None:
    """Restore a :func:`cache_state` snapshot (keys migrated like the JSON
    cache; sweep entries outrank model-derived in-process ones)."""
    for k, v in state.items():
        k = _migrate_key(k)
        if k not in _CACHE or _CACHE[k].get("source") != "sweep":
            _CACHE[k] = dict(v)
