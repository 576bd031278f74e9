"""solver.readbacks.solve: the device-to-host transfers a solve makes, the
mean over the window's decisions that carry the program's own count
(``profile["readbacks"]``, one for each batched ``jax.device_get``; a
re-solve with nothing dirty returns before the ladder and carries none).
Each is a round trip that leaves the device idle while the host waits for
it. None where no decision carries the count."""


def read(record, suffix):
    if record.get("kind") != "dag":
        return None
    counts = [(dec.profile or {}).get("readbacks")
              for _, dec in record.get("log", ())]
    counts = [c for c in counts if c is not None]
    return sum(counts) / len(counts) if counts else None
