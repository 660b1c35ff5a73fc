"""The whole search's share of the chips' peak, in %: the least time the
search's required work could take on the cell's chips (2*B*N*d operations
at the bf16 peak, or every row read once at its stored bit width plus the
k*B rescored rows' cells at the HBM bandwidth, whichever is longer),
over the window's host time per batch."""

from bench import work


def read(r):
    c = r.window["counts"]
    if not c.get("batches"):
        return None
    wl = r.ctx.workload
    ops, nbytes = work.search_work(r.ctx.config, wl["batch"], wl["k"],
                                   wl["mode"])
    least = work.least_seconds(ops / r.ctx.chips, nbytes / r.ctx.chips,
                               r.peak)
    return 100.0 * least / (c["seconds"] / c["batches"])
