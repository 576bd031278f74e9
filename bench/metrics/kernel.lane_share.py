"""kernel.lane_share: the share of the ``frontier_grid`` programs' lanes
that hold a row's channel slot, in percent, over the window's solves, from
the program's own count of each rung's launches (``profile["launches"]``
of each decision): 100 sum launches rows pack / sum launches lanes. A
program of ``block_f`` rows fills 128 * ceil(block_f * pack / 128) lanes,
and packing deals each row's channels over ``pack`` of them. None where
the decisions carry no lane count (a program that does not pack)."""


def read(record, suffix):
    if record.get("kind") != "dag":
        return None
    used = lanes = 0
    for _, dec in record.get("log", ()):
        for e in (dec.profile or {}).get("launches", ()):
            if "lanes" not in e or "pack" not in e:
                continue
            used += e["launches"] * e["rows"] * e["pack"]
            lanes += e["launches"] * e["lanes"]
    return 100.0 * used / lanes if lanes else None
