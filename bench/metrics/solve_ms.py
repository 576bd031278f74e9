"""solve_ms: the window's milliseconds over the solves completed in it (one
caller, each solve sent when the last returned)."""


def read(record, suffix):
    if record.get("kind") != "dag" or not record.get("solves"):
        return None
    return record["window_s"] * 1e3 / record["solves"]
