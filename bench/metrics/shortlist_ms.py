"""Device time per batch of the phase-1 shortlist: operations under the
`shortlist_fused` scope (kernels/shortlist.py), per chip, in ms."""


def read(r):
    c = r.window["counts"]
    if not c.get("batches") or not r.trace.devices:
        return None
    s = r.trace.scope_seconds("shortlist_fused")
    return 1e3 * s / c["batches"] if s > 0 else None
