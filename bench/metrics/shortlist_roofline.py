"""The phase-1 shortlist's share of its roofline, in %: the least time
its work could take on one chip (2*B*N_chip*d operations at the bf16 peak,
or the chip's N_chip rows read once at their stored bit width at the HBM
bandwidth, whichever is longer) over its device time per batch under the
`shortlist_fused` scope."""

from bench import work


def read(r):
    c = r.window["counts"]
    if not c.get("batches") or not r.trace.devices:
        return None
    s = r.trace.scope_seconds("shortlist_fused")
    if s <= 0:
        return None
    ops, nbytes = work.shortlist_work(r.ctx.config, r.ctx.workload["batch"])
    least = work.least_seconds(ops / r.ctx.chips, nbytes / r.ctx.chips,
                               r.peak)
    return 100.0 * least / (s / c["batches"])
