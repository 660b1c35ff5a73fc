"""Device time per decode step of the kNN head: leaf ops under the
`knn_head` scope (query, vote softmax, mixture, greedy token) and under
the store search's own `search_*` scopes inside it, in ms
(bench/leaf_layers.py)."""

from bench import leaf_layers


def read(r):
    ms = leaf_layers.per_step_ms(r)
    return leaf_layers.knn_ms(ms) if ms else None
