"""The kNN head's share of its roofline, in %: the least time of one
step's search (bench/work.py `search_work` on the configuration's store
keys: every stored row met by every query and read once at its stored
bit width, plus the k rescored rows' cells) over `knn_ms`, the device time
per step under `knn_head` and the `search_*` scopes."""

from bench import leaf_layers, work


def read(r):
    ms = leaf_layers.per_step_ms(r)
    knn = leaf_layers.knn_ms(ms) if ms else None
    if not knn:
        return None
    cfg, wl = r.ctx.config, r.ctx.workload
    least = work.least_seconds(*work.search_work(
        cfg, wl["batch"], cfg["knn"]["k"], wl["mode"]), r.peak)
    return 100.0 * least / (1e-3 * knn)
