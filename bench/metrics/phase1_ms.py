"""Device time per batch of the program's phase-1 shortlist layer: every op
under the `search_shortlist` scope, whatever implements it (the fused
kernel, a dense matmul), and the copies XLA inserts for it, per chip, in
ms (bench/layers.py)."""

from bench import layers


def read(r):
    ms = layers.per_batch_ms(r)
    return ms.get("search_shortlist") if ms else None
