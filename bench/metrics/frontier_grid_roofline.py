"""frontier_grid_roofline.solve: the ``frontier_grid`` kernel's share of
its roofline over the traced window, in percent: the least time the chip
could take for the kernel's launches (the larger of their
operations over the peak rate and their bytes over the HBM bandwidth,
``bench/kernels/frontier_grid.py``, ``bench/peaks.py``) over the device
time of the trace events named ``frontier_grid_*``.

An event's rows and channels come from its HLO text; its grid points from
its place in the solve it ran in (``bench.solve`` annotation), since the
launches are inside the solver's compiled ladder: the grad launches before
the first forward launch are the presolve, that forward launch is the
triage, the grad launches after it the refine, the last forward launch the
final score (``ladder_t`` of the record). A solve whose launches do not
follow that order is left out, with its time.

None when no event has a shape.
"""
from bench.kernels import frontier_grid as kfg
from bench.peaks import peaks


def _by_ladder(tr, ladder):
    for _, a0, b0 in tr["annotations"]:
        evs = [e for e in tr["kernel_events"] if a0 <= e[1] and e[2] <= b0]
        kinds = [kfg.parse_name(e[0]) for e in evs]
        fwd = [i for i, k in enumerate(kinds) if k and k[0] == "fwd"]
        if len(fwd) != 2 or any(k is None for k in kinds) or \
                any(e[3] is None for e in evs):
            continue
        for i, (name, a, b, rows, k) in enumerate(evs):
            if i <= fwd[0]:
                T = ladder["presolve"]
            elif i < fwd[1]:
                T = ladder["refine"]
            else:
                T = ladder["final"]
            yield name, rows, k, T, b - a


def read(record, suffix):
    tr = record.get("trace")
    if not tr or not tr.get("kernel_events") or not record.get("ladder_t"):
        return None
    peak = peaks(record["device_kind"])
    least = took = 0.0
    for name, rows, k, T, dur_ns in _by_ladder(tr, record["ladder_t"]):
        kind = kfg.parse_name(name)
        if kind is None:
            continue
        mode, family = kind
        least += max(kfg.ops(rows, k, T, mode) / peak["flops_per_s"],
                     kfg.bytes_moved(rows, k, mode, family)
                     / peak["hbm_bytes_per_s"])
        took += dur_ns / 1e9
    return 100.0 * least / took if took > 0 else None
