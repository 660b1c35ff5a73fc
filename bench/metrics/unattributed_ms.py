"""Device time per batch of ops that map to no layer of the program (no
layer scope covers them, or their module's HLO is not in the profile), per
chip, in ms (bench/layers.py)."""

from bench import layers


def read(r):
    ms = layers.per_batch_ms(r)
    return ms.get(layers.UNATTRIBUTED, 0.0) if ms else None
