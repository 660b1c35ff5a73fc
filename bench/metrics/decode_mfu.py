"""The whole kNN-LM decode step's share of the chip's peak, in %: the least
time of the step's required work -- the decoder's (every weight and the
full cache ring read once, bench/work_decoder.py) plus the kNN head's
search (bench/work.py, on the configuration's store keys) -- over the
window's host time per step."""

from bench import work, work_decoder


def step_work(r) -> tuple[float, float]:
    """(ops, bytes) of one decode step: the decoder and the search."""
    cfg, wl = r.ctx.config, r.ctx.workload
    dec = cfg["decoder"]
    slots = work_decoder.cache_slots(dec, dec["max_position_embeddings"])
    d_ops, d_bytes = work_decoder.decode_work(dec, wl["batch"], slots)
    k_ops, k_bytes = work.search_work(cfg, wl["batch"], cfg["knn"]["k"],
                                      wl["mode"])
    return d_ops + k_ops, d_bytes + k_bytes


def read(r):
    c = r.window["counts"]
    if not c.get("steps"):
        return None
    least = work.least_seconds(*step_work(r), r.peak)
    return 100.0 * least / (c["seconds"] / c["steps"])
