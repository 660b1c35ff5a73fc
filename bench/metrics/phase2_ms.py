"""Device time per batch of the program's phase-2 rescore layer: every op
under the `search_rescore` scope (the query and store string grids, the
gather of the shortlisted rows, the noisy string model) and the copies XLA
inserts for it, per chip, in ms (bench/layers.py)."""

from bench import layers


def read(r):
    ms = layers.per_batch_ms(r)
    return ms.get("search_rescore") if ms else None
