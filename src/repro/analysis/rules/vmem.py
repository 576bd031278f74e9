"""VMEM/BlockSpec audit for Pallas launch wrappers (RPA030-RPA032).

ROADMAP item 2 flagged the fused-pgrad six-accumulator working set as a
latent hazard: a ``block_f`` default that fits the forward kernel can
overflow VMEM the moment differentiation swaps in the full-parameter fused
launch. This rule runs the SAME working-set model the runtime autotuner uses
(:func:`repro.kernels.autotune.vmem_bytes`) at lint time, over every
family x mode combination (shared and per-row statistics occupy the same
(K, block_f) tiles, so the layout adds no axis), so the "pgrad needs its
own safe block" footnote is a hard check instead of tribal knowledge.

A *launch wrapper* is any function whose body calls ``pl.pallas_call``. Its
modes come from its signature: a ``param_grads`` parameter means the fused
kernel (``grad`` and ``pgrad`` modes), otherwise forward-only. The audit
point is the repo's reference fleet shape K=1024 channels x T=1024 grid
points — the documented scale target every default must survive.

* **RPA030** — the wrapper's default ``block_f`` overflows the VMEM budget
  for at least one audited combination; the message names every failing
  (family, mode) pair and the largest candidate block that fits
  them all.
* **RPA031** — the wrapper derives its grid from ``block_f`` (``F //
  block_f``) but neither it nor a same-file helper it passes ``block_f`` to
  performs a divisibility check (``%``): a non-multiple F silently drops the
  tail rows of the launch.
* **RPA032** — NO candidate block fits some audited combination: the kernel
  cannot launch at reference scale at all and the budget model or kernel
  working set needs rework.
"""
from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Tuple

from ..framework import Finding, Project, call_name, param_names, register

# reference fleet shape the defaults must survive (see module docstring)
_AUDIT_K = 1024
_AUDIT_T = 1024


def _audit_modes(has_param_grads: bool) -> List[Tuple[str, bool, bool]]:
    if has_param_grads:
        return [("grad", True, False), ("pgrad", True, True)]
    return [("fwd", False, False)]


def _block_f_default(fn) -> Optional[int]:
    """The int default of the wrapper's ``block_f`` parameter, if any."""
    a = fn.args
    pos = a.posonlyargs + a.args
    for param, default in zip(pos[len(pos) - len(a.defaults):], a.defaults):
        if param.arg == "block_f" and isinstance(default, ast.Constant) \
                and isinstance(default.value, int):
            return default.value
    for param, default in zip(a.kwonlyargs, a.kw_defaults):
        if param.arg == "block_f" and isinstance(default, ast.Constant) \
                and isinstance(default.value, int):
            return default.value
    return None


def _calls_pallas(fn) -> Optional[ast.Call]:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) and call_name(node) == "pallas_call":
            return node
    return None


def _has_mod_on(fn, name: str) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod):
            for side in (node.left, node.right):
                if isinstance(side, ast.Name) and side.id == name:
                    return True
    return False


def _grid_uses(call: ast.Call, name: str) -> bool:
    for kw in call.keywords:
        if kw.arg != "grid":
            continue
        for node in ast.walk(kw.value):
            if isinstance(node, ast.Name) and node.id == name:
                return True
    return False


@register
class VmemBlockSpecRule:
    CODES = {
        "RPA030": "default block_f overflows the VMEM working-set budget",
        "RPA031": "grid derived from block_f without a divisibility guard",
        "RPA032": "no candidate block_f fits the VMEM budget at all",
    }

    def run(self, project: Project) -> Iterator[Finding]:
        # imported lazily so the linter works (minus this rule's model) even
        # when jax is absent from the interpreter running it
        try:
            from repro.core.distributions import FAMILIES
            from repro.kernels import autotune
        except ImportError:
            return
        budget = autotune._VMEM_BUDGET_BYTES

        for ctx in project.files:
            defs = {n.name: n for n in ast.walk(ctx.tree)
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
            for fn in defs.values():
                pallas = _calls_pallas(fn)
                if pallas is None:
                    continue
                yield from self._check_guard(ctx, fn, defs, pallas)
                bf = _block_f_default(fn)
                if bf is None:
                    continue
                yield from self._check_budget(ctx, fn, bf, FAMILIES,
                                              autotune, budget)

    def _check_guard(self, ctx, fn, defs, pallas) -> Iterator[Finding]:
        if not _grid_uses(pallas, "block_f"):
            return
        if _has_mod_on(fn, "block_f"):
            return
        # a same-file helper the wrapper hands block_f to may own the check
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                helper = defs.get(call_name(node) or "")
                if helper is None or helper is fn:
                    continue
                passes_bf = any(isinstance(a, ast.Name) and a.id == "block_f"
                                for a in node.args) or \
                    any(isinstance(kw.value, ast.Name)
                        and kw.value.id == "block_f"
                        for kw in node.keywords)
                if passes_bf and any(_has_mod_on(helper, p)
                                     for p in param_names(helper.args)):
                    return
        yield ctx.finding(
            fn, "RPA031",
            f"'{fn.name}' launches with grid derived from block_f but never "
            f"checks F % block_f — a non-multiple F silently drops rows")

    def _check_budget(self, ctx, fn, bf, families, autotune,
                      budget) -> Iterator[Finding]:
        modes = _audit_modes("param_grads" in param_names(fn.args))
        combos = [(fam, mode, fused, params) for fam in families
                  for mode, fused, params in modes]

        def need(c, fam, fused, params):
            return autotune.vmem_bytes(c, _AUDIT_K, _AUDIT_T, fused, fam,
                                       params)

        failing = [(fam, mode, need(bf, fam, fused, params))
                   for fam, mode, fused, params in combos
                   if need(bf, fam, fused, params) > budget]
        infeasible = [(fam, mode) for fam, mode, fused, params in combos
                      if all(need(c, fam, fused, params) > budget
                             for c in autotune.BLOCK_F_CANDIDATES)]
        if failing:
            safe = [c for c in autotune.BLOCK_F_CANDIDATES
                    if all(need(c, fam, fused, params) <= budget
                           for fam, _, fused, params in combos)]
            listed = ", ".join(f"{fam}/{mode}={n / 2**20:.1f}MB"
                               for fam, mode, n in failing[:4])
            more = f" (+{len(failing) - 4} more)" if len(failing) > 4 else ""
            hint = (f"largest block fitting every combo is {max(safe)}"
                    if safe else "no candidate fits every combo")
            yield ctx.finding(
                fn, "RPA030",
                f"'{fn.name}' default block_f={bf} overflows the "
                f"{budget / 2**20:.1f}MB VMEM budget at "
                f"K={_AUDIT_K}/T={_AUDIT_T} for {listed}{more}; {hint}")
        for fam, mode in infeasible:
            yield ctx.finding(
                fn, "RPA032",
                f"'{fn.name}': no candidate block_f fits the VMEM budget for "
                f"{fam}/{mode} at K={_AUDIT_K}/T={_AUDIT_T} — working set "
                f"needs rework")
