"""The phase-1 shortlist layer's share of its roofline, in %: the least
time its work could take on one chip (2*B*N_chip*d operations at the bf16
peak, or the chip's N_chip rows read once at their stored bit width at the
HBM bandwidth, whichever is longer) over `phase1_ms`, the device time per
batch of the `search_shortlist` layer."""

from bench import layers, work


def read(r):
    ms = layers.per_batch_ms(r)
    if not ms or not ms.get("search_shortlist"):
        return None
    ops, nbytes = work.shortlist_work(r.ctx.config, r.ctx.workload["batch"])
    least = work.least_seconds(ops / r.ctx.chips, nbytes / r.ctx.chips,
                               r.peak)
    return 100.0 * least / (1e-3 * ms["search_shortlist"])
