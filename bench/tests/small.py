"""Small sizes at which the CPU tests drive whole runs of the cells."""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def config(name: str) -> dict:
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def epigenomics() -> dict:
    """2 lanes of 12 chunks, short ladders, on the XLA path.

    At this size a sound solve beats the best plain split by less than at
    the cell's own (``descent`` 0.91-0.93 on three seeds here, 0.80-0.83
    at the cell's size; 1 for a solve that returns its start), so the small
    run holds ``descent`` to a limit of its own."""
    cfg = config("epigenomics")
    chk = cfg["check"]
    return {"workflow": dict(cfg["workflow"], lanes=2, chunks=24),
            "solve": dict(cfg["solve"], steps=24, num_t=32, impl="xla"),
            "check": dict(chk, mc_trials=0, solves=3,
                          limits=dict(chk["limits"], descent=0.97)),
            "warm_requests": 1}


def overrides(cell: str) -> dict:
    return {"epigenomics.cold": epigenomics}[cell]()
