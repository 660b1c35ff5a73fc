"""Device time per decode step of the decoder: every leaf op under the
`decoder` scope (embedding, every layer, final norm, LM head and its
log-softmax) and the copies XLA inserts for it, in ms
(bench/leaf_layers.py)."""

from bench import leaf_layers


def read(r):
    ms = leaf_layers.per_step_ms(r)
    return ms.get("decoder") if ms else None
