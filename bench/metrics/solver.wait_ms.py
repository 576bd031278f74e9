"""solver.wait_ms.solve: the solver's waits on the device, in ms a solve:
the ``solver.wait`` spans (``repro.obs``, recorded in ``--trace 1``
runs: each ``block_until_ready`` and read-back of the ladder), over the
solves of the window. None where the program records no waits."""


def read(record, suffix):
    if record.get("kind") != "dag" or not record.get("solves"):
        return None
    waits = [r["dur_us"] for r in record.get("spans", ())
             if r.get("type") == "span" and r["name"] == "solver.wait"]
    return sum(waits) / record["solves"] / 1e3 if waits else None
