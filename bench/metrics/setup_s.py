"""setup_s: seconds from the harness's start to the window's opening:
imports, device start, building the system from the seed, compiling or
loading every program the cell's traffic uses, and the warm-up traffic."""


def read(record, suffix):
    return record["setup_s"]
