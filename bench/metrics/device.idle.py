"""device.idle.solve: one less the busy union of the device's
operations over the traced window, in percent (profiler trace)."""


def read(record, suffix):
    tr = record.get("trace")
    if not tr or tr.get("idle_share") is None:
        return None
    return 100.0 * tr["idle_share"]
