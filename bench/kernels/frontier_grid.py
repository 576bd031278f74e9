"""Operations and bytes of one ``frontier_grid`` launch, from its shapes.

The kernel evaluates, for F candidate rows of K channels on a T-point time
grid, the family CDF of every channel at every grid point, sums their logs,
and integrates the survival function (``bench/reference/frontier.py`` has
the same arithmetic written out). Counting the elementwise operations of
that arithmetic as written, per grid point and channel:

* the standardized argument: a subtraction and a division (2);
* the normal CDF as the kernel evaluates it (an erfc Chebyshev fit): an
  absolute value, a scale, the reciprocal ``1/(1 + x/2)`` (3), a degree-9
  polynomial by Horner (18), ``exp`` of it less ``x^2`` (3), the scale and
  the reflection for negative arguments (4): 28;
* the clamp, the log and the sum over channels (4);

34 in all for the forward pass (``fwd``); per grid point the joint CDF's
``exp``, the survival and the two trapezoid sums add 6. The fused modes
also form, per grid point and channel, the density (4), the clip gate (3),
the ratio to the CDF (1), the trapezoid weight (2) and the accumulators:
two multiply-adds each for four of them in ``grad`` (8) and six in
``pgrad`` (12). A transcendental counts as one operation. The lognormal
and drift families add work per (row, channel) or per (row, point), which
is left out: it is below one part in K or T of the total.

Bytes are what the launch has to move at least once: the split, the
per-row means and deviations and the family parameters in, the moments
(and, fused, the two gradients per channel) out, all float32.
"""
from __future__ import annotations

FWD_POINT = 34
GRID_POINT = 6
FUSED_POINT = {"grad": 18, "pgrad": 22}
EXTRA_ROWS = {"normal": 1, "lognormal": 1, "drift": 1, "defective": 2,
              "empirical": 9}


def ops(F: int, K: int, T: int, mode: str) -> float:
    per_point = FWD_POINT + (FUSED_POINT[mode] if mode != "fwd" else 0)
    return float(F) * T * (K * per_point + GRID_POINT)


def bytes_moved(F: int, K: int, mode: str, family: str) -> float:
    inputs = F * K * (3 + EXTRA_ROWS.get(family, 1))
    outputs = 2 * F + (0 if mode == "fwd" else 2 * F * K)
    return 4.0 * (inputs + outputs)


def parse_name(name: str):
    """(mode, family) of a kernel event name ``frontier_grid_<mode>_<family>``,
    or None for any other name."""
    if not name.startswith("frontier_grid_"):
        return None
    parts = name[len("frontier_grid_"):].split("_")
    if len(parts) < 2 or parts[0] not in ("fwd", "grad", "pgrad"):
        return None
    return parts[0], parts[1]
