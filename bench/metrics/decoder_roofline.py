"""The decoder's share of its roofline, in %: the least time of one step's
decoder work (a multiply and an add per weight and sequence, attention
over the full ring; every weight and the ring read once, at the HBM
bandwidth or the bf16 peak, whichever is longer; bench/work_decoder.py)
over `decoder_ms`."""

from bench import leaf_layers, work, work_decoder


def read(r):
    ms = leaf_layers.per_step_ms(r)
    if not ms or not ms.get("decoder"):
        return None
    dec = r.ctx.config["decoder"]
    slots = work_decoder.cache_slots(dec, dec["max_position_embeddings"])
    least = work.least_seconds(*work_decoder.decode_work(
        dec, r.ctx.workload["batch"], slots), r.peak)
    return 100.0 * least / (1e-3 * ms["decoder"])
