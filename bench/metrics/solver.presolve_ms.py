"""solver.presolve_ms.solve: mean per solve of the solver's ``presolve`` phase
(``DAGDecision.profile["phase_us"]``, host clock), in ms, over the
solves of the window that ran it."""


def read(record, suffix):
    if record.get("kind") != "dag":
        return None
    d = [p["presolve"] for p in record.get("phase_us", ()) if "presolve" in p]
    return sum(d) / len(d) / 1e3 if d else None
