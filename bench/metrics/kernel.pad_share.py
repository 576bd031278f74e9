"""kernel.pad_share.solve: the share of the ``frontier_grid`` kernel's
row and channel slots that is padding, in percent, over the window's
solves, from the program's own count of each rung's launches
(``profile["launches"]`` of each decision): 100 (1 - sum launches channels
num_t / sum launches rows_padded k num_t). A row pads to the widest stage's
``k`` channels, and a launch's rows to a multiple of its block. The lanes
that the kernel pads each block to (rows lie on 128-lane vregs, so a block
of 135 rows fills 256 lanes) are not counted: ``block_f`` in each entry
gives them. None where the decisions carry no count."""


def read(record, suffix):
    if record.get("kind") != "dag":
        return None
    real = slots = 0
    for _, dec in record.get("log", ()):
        for e in (dec.profile or {}).get("launches", ()):
            real += e["launches"] * e["channels"] * e["num_t"]
            slots += e["launches"] * e["rows_padded"] * e["k"] * e["num_t"]
    return 100.0 * (1.0 - real / slots) if slots else None
