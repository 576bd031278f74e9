"""Checkpointing: sharded npz + JSON metadata, atomic pointer, async writer.

Layout:
    <dir>/step_000123/arrays.npz      flattened pytree leaves (key = json path)
    <dir>/step_000123/meta.json       step, rng seed, scheduler posteriors, ...
    <dir>/LATEST                      atomic pointer file (rename-committed)

Restore is exact: pytree structure is rebuilt from the saved key paths and
every leaf is bit-compared in tests. The scheduler's NIG posteriors ride in
meta.json so a restarted job keeps its learned channel statistics (the paper's
on-the-fly estimates survive failures).

Whole-pipeline checkpoints (:func:`save_pipeline` / :func:`restore_pipeline`)
bundle everything a partitioning loop owns into ONE manifest: the balancer's
state_dict (posteriors, family selection + hysteresis, cached solve, cadence
phase), any in-flight per-channel progress, and the autotune cache snapshot.

Kill/restore tick-parity contract: a replica killed after its step-t
checkpoint and restored from it produces a bitwise-identical step t+1 —
same weights, same family selection, same posterior update — because every
input to the next tick (balancer state, solver warm start, autotune plan
choice) is either in the manifest or deterministic code. Enforced by
``tests/test_fault.py``; breaking it means a failover replays a DIFFERENT
schedule than the primary would have run.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Optional, Tuple

import jax
import numpy as np

__all__ = ["save", "restore", "latest_step", "save_pipeline",
           "restore_pipeline", "CheckpointManager"]

_SEP = "/"


def _pipeline_kind(balancer) -> str:
    """Manifest kind for a pipeline snapshot — dispatch is by state SHAPE.

    "engine" = the continuous-batching WorkflowEngine (restores against
    code-side templates), "workflow" = the per-stage WorkflowBalancer
    (restores against its DAG), "balancer" = any single-fleet decider with
    a ``UncertaintyAwareBalancer``-shaped state_dict (the batcher included).
    """
    name = type(balancer).__name__
    if name == "WorkflowEngine":
        return "engine"
    return "workflow" if name == "WorkflowBalancer" else "balancer"


def _flatten(tree) -> dict:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def _unflatten_like(template, flat: dict):
    paths_leaves = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in paths_leaves[0]:
        key = _SEP.join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        if key not in flat:
            raise ValueError(
                f"checkpoint restore: leaf {key!r} missing from the saved "
                f"arrays (template and checkpoint structures diverged; "
                f"saved keys: {sorted(flat)[:8]}...)")
        arr = flat[key]
        if tuple(arr.shape) != tuple(np.shape(leaf)):
            # a bare assert here vanished under `python -O` and surfaced as
            # a reshape error three layers up — name the leaf and both shapes
            raise ValueError(
                f"checkpoint restore: leaf {key!r} shape mismatch — "
                f"expected {tuple(np.shape(leaf))} (template), found "
                f"{tuple(arr.shape)} (checkpoint); the run being restored "
                f"was saved with a different fleet/model shape")
        dtype = np.asarray(leaf).dtype
        if arr.dtype.kind == "V" and arr.dtype.itemsize == dtype.itemsize:
            # npz keeps extension dtypes (bfloat16) as raw bytes; reinterpret
            arr = arr.view(dtype)
        leaves.append(arr.astype(dtype))
    return jax.tree_util.tree_unflatten(paths_leaves[1], leaves)


def save(directory: str, step: int, tree, meta: Optional[dict] = None) -> str:
    """Write checkpoint for ``step``; commit via atomic LATEST rename."""
    os.makedirs(directory, exist_ok=True)
    name = f"step_{step:08d}"
    tmp = os.path.join(directory, f".tmp_{name}")
    final = os.path.join(directory, name)
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    np.savez(os.path.join(tmp, "arrays.npz"), **_flatten(tree))
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump({"step": step, **(meta or {})}, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)
    ptr_tmp = os.path.join(directory, ".LATEST.tmp")
    with open(ptr_tmp, "w") as f:
        f.write(name)
    os.replace(ptr_tmp, os.path.join(directory, "LATEST"))
    return final


def latest_step(directory: str) -> Optional[int]:
    """Step of the LATEST pointer, or None when there is no usable one.

    A corrupt or empty pointer (the crash the atomic rename protects against
    landed mid-write anyway — power loss between rename and fsync, or a
    truncated copy) falls back to the newest complete step directory on
    disk instead of raising: restore-after-crash is exactly when this path
    runs, and a garbage pointer must not make a good checkpoint unreachable.
    """
    ptr = os.path.join(directory, "LATEST")
    if os.path.exists(ptr):
        try:
            with open(ptr) as f:
                text = f.read().strip()
            if text:
                return int(text.split("_")[-1])
        except (OSError, ValueError):
            pass
    # pointer missing/corrupt: scan for complete step dirs (meta.json is
    # written last inside the tmp dir, so its presence marks completeness)
    if not os.path.isdir(directory):
        return None
    steps = []
    for d in os.listdir(directory):
        if d.startswith("step_") and os.path.exists(
                os.path.join(directory, d, "meta.json")):
            try:
                steps.append(int(d.split("_")[-1]))
            except ValueError:
                continue
    return max(steps) if steps else None


def restore(directory: str, template, step: Optional[int] = None) -> Tuple[Any, dict]:
    """Load (tree, meta); ``template`` supplies structure/dtypes/shapes."""
    step = step if step is not None else latest_step(directory)
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {directory}")
    path = os.path.join(directory, f"step_{step:08d}")
    with np.load(os.path.join(path, "arrays.npz")) as z:
        flat = {k: z[k] for k in z.files}
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    return _unflatten_like(template, flat), meta


def save_pipeline(directory: str, step: int, balancer, *,
                  inflight: Optional[dict] = None, autotune: bool = True,
                  tree=None, meta: Optional[dict] = None) -> str:
    """One crash-consistent manifest for a whole partitioning pipeline.

    Bundles, in a single atomically-committed step directory:

    * ``balancer.state_dict()`` — posteriors, family selection/hysteresis,
      cached solve + key, refresh cadence phase, failure sets;
    * ``inflight`` — per-channel progress of the currently executing step
      ({"done": ..., "failed": ...} or any JSON-serializable dict), so a
      restore can re-price the remaining work via ``resolve_inflight``;
    * the process-wide autotune cache (``kernels.autotune.cache_state()``),
      so the restored replica re-runs the SAME kernel plans — plan choice
      affects float reduction order, and tick parity is bitwise;
    * optionally an arbitrary array ``tree`` (model state) alongside.

    See the module docstring for the kill/restore tick-parity contract this
    manifest exists to uphold. Restore with :func:`restore_pipeline`.
    """
    from ..kernels import autotune as _autotune  # lazy: layering
    from ..obs import events as _obs_events  # lazy: layering
    manifest = {
        "kind": _pipeline_kind(balancer),
        "balancer": balancer.state_dict(),
        "inflight": inflight,
        "autotune": _autotune.cache_state() if autotune else None,
    }
    path = save(directory, step, tree if tree is not None else {},
                meta={**(meta or {}), "pipeline": manifest})
    _obs_events.ckpt_save(step, manifest["kind"], path)
    return path


def restore_pipeline(directory: str, *, dag=None, template=None,
                     templates=None, step: Optional[int] = None,
                     autotune: bool = True):
    """Restore a :func:`save_pipeline` manifest.

    Returns ``(balancer, inflight, meta)`` (plus the restored ``tree`` in
    ``meta["tree"]`` when a ``template`` is supplied). ``dag`` is required
    for workflow-kind checkpoints and ``templates`` (name -> StageDAG) for
    engine-kind ones — graph structure is code-side configuration, only the
    learned/derived state rides in the manifest. When ``autotune`` is True
    the saved kernel-plan cache is loaded into the process so the next tick
    runs identical plans (the bitwise half of the parity contract).
    """
    from ..sched.balancer import (UncertaintyAwareBalancer,
                                  WorkflowBalancer)  # lazy: layering
    tree, meta = restore(directory, template if template is not None else {},
                         step=step)
    manifest = meta.get("pipeline")
    if manifest is None:
        raise ValueError(
            f"checkpoint in {directory} has no 'pipeline' manifest — it was "
            f"written by save(), not save_pipeline()")
    if manifest["kind"] == "engine":
        from ..serve.engine import WorkflowEngine  # lazy: layering
        if templates is None:
            raise ValueError("engine-kind checkpoint needs the templates= "
                             "mapping the engine was built against")
        balancer = WorkflowEngine.from_state_dict(manifest["balancer"],
                                                  templates)
    elif manifest["kind"] == "workflow":
        if dag is None:
            raise ValueError("workflow-kind checkpoint needs the dag= the "
                             "balancer was built against")
        balancer = WorkflowBalancer.from_state_dict(manifest["balancer"], dag)
    else:
        balancer = UncertaintyAwareBalancer.from_state_dict(
            manifest["balancer"])
    if autotune and manifest.get("autotune"):
        from ..kernels import autotune as _autotune  # lazy: layering
        _autotune.load_cache_state(manifest["autotune"])
    from ..obs import events as _obs_events  # lazy: layering
    _obs_events.ckpt_restore(int(meta.get("step", -1)), manifest["kind"],
                             directory)
    if template is not None:
        meta = dict(meta)
        meta["tree"] = tree
    return balancer, manifest.get("inflight"), meta


class CheckpointManager:
    """Interval-based async checkpointing with bounded retention."""

    def __init__(self, directory: str, interval: int = 100, keep: int = 3):
        self.dir = directory
        self.interval = interval
        self.keep = keep
        self._thread: Optional[threading.Thread] = None

    def maybe_save(self, step: int, tree, meta: Optional[dict] = None,
                   blocking: bool = False) -> bool:
        if step % self.interval != 0:
            return False
        host_tree = jax.tree.map(np.asarray, tree)  # device->host before async
        if self._thread is not None:
            self._thread.join()

        def work():
            save(self.dir, step, host_tree, meta)
            self._gc()

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()
        if blocking:
            self.wait()
        return True

    def maybe_save_pipeline(self, step: int, balancer, *,
                            inflight: Optional[dict] = None, tree=None,
                            meta: Optional[dict] = None,
                            blocking: bool = False) -> bool:
        """Interval-gated :func:`save_pipeline` through the async writer.

        The balancer state_dict and autotune snapshot are captured on the
        CALLER's thread — the manifest reflects this exact tick boundary
        even if the balancer keeps mutating while the write runs.
        """
        if step % self.interval != 0:
            return False
        from ..kernels import autotune as _autotune  # lazy: layering
        manifest = {
            "kind": _pipeline_kind(balancer),
            "balancer": balancer.state_dict(),
            "inflight": inflight,
            "autotune": _autotune.cache_state(),
        }
        host_tree = (jax.tree.map(np.asarray, tree)
                     if tree is not None else {})
        if self._thread is not None:
            self._thread.join()

        def work():
            save(self.dir, step, host_tree,
                 meta={**(meta or {}), "pipeline": manifest})
            self._gc()

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()
        if blocking:
            self.wait()
        return True

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self):
        steps = sorted(int(d.split("_")[-1]) for d in os.listdir(self.dir)
                       if d.startswith("step_"))
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:08d}"),
                          ignore_errors=True)
