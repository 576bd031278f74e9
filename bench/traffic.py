"""The one traffic generator: turns a mix file of parameters into requests.

A mix (``bench/traffic/<name>.json``) is data only. ``"loop": "closed"`` is
one caller that sends its next request when the last one returns. Each
request scales the statistics of every stage by a factor of its own, drawn
uniformly from ``scale``. ``warm_requests`` requests of the same mix, on a
seed of their own, run before the window.

The seed fixes everything: the same seed gives the same requests.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Iterator

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if mix.get("loop") != "closed":
        raise ValueError(f"mix {name!r}: only a closed loop is generated, "
                         f"not {mix.get('loop')!r}")
    return mix


@dataclass(frozen=True)
class Rescale:
    factors: np.ndarray        # (S,) multiplier of each stage's statistics


def closed_loop(mix: dict, rng, n_stages: int) -> Iterator[Rescale]:
    """Endless requests of the closed loop."""
    lo, hi = mix["scale"]
    while True:
        yield Rescale(rng.uniform(lo, hi, n_stages))
