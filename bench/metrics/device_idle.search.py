"""Share of the search window in which no operation ran on the chips
(1 - union of device-op intervals / traced window), in %."""


def read(r):
    if not r.trace.devices:
        return None
    return 100.0 * r.trace.idle_share
