"""solver.fixed_stages: the stages a solve composes as constants, the mean
over the window's decisions that carry the program's own count
(``profile["fixed_stages"]``: the single-channel stages, whose split is
[1] and which are no PGD row; a re-solve with nothing dirty returns before
the ladder and carries none). Each of them would otherwise be a row of
every PGD launch and of the projection's sort, padded to the widest
stage. None where no decision carries the count."""


def read(record, suffix):
    if record.get("kind") != "dag":
        return None
    counts = [(dec.profile or {}).get("fixed_stages")
              for _, dec in record.get("log", ())]
    counts = [c for c in counts if c is not None]
    return sum(counts) / len(counts) if counts else None
