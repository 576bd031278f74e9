"""solver.host_ms.solve: the host's own work inside the solver, in ms a
solve: the ``solver.phase`` spans less the ``solver.wait`` spans inside
them (``repro.obs``, recorded in ``--trace 1`` runs), over the solves of
the window. In that time the solver has nothing queued on the device:
building the starts, dispatching the ladder's programs, triage's dedupe,
the final pick. None where the program records no waits."""


def read(record, suffix):
    if record.get("kind") != "dag" or not record.get("solves"):
        return None
    spans = [r for r in record.get("spans", ()) if r.get("type") == "span"]
    waits = [r["dur_us"] for r in spans if r["name"] == "solver.wait"]
    if not waits:
        return None
    phases = sum(r["dur_us"] for r in spans if r["name"] == "solver.phase")
    return (phases - sum(waits)) / record["solves"] / 1e3
