"""dispatch.compiles.solve: programs compiled, or read back from
the persistent cache, inside the window (JAX's monitoring events). Set-up
warms every shape the traffic uses, so this should read 0."""


def read(record, suffix):
    return float(record["compiles"])
