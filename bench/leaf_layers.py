"""Device time per step in each layer of the program, counting leaf ops
only.

A program with control flow (the decoder's scan over its layers is an XLA
`while`) shows the control-flow op itself on the trace's `XLA Ops` line,
as one event spanning the body ops that run inside it, which are events
of their own. `bench/layers.py` attributes every op event and would count
that time twice. Here an event that holds another event of the same chip
inside its interval is a container and is left out; the rest go to layers
as `bench/layers.py` attributes them (`layers.load`, `layers.
layer_seconds`).
"""

from __future__ import annotations

import dataclasses

from bench import layers


def leaves(events: list) -> list:
    """The events of one chip's `XLA Ops` line that contain no other. In
    start order (a container before the body op that starts with it), a
    container is followed by the first op inside it."""
    ordered = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    nxt = ordered[1:] + [None]
    return [e for e, f in zip(ordered, nxt)
            if f is None or f.start_ns >= e.end_ns or f.end_ns > e.end_ns]


def per_step_ms(r) -> dict | None:
    """{layer: device ms per step} of the traced window, leaf ops only
    (cached on the reading); None where there is nothing to read."""
    if "_leaf_layer_ms" not in vars(r):
        steps = r.window["counts"].get("steps")
        found = layers.load(r) if steps and r.trace.devices else None
        ms = None
        if found is not None:
            tr = dataclasses.replace(r.trace, devices={
                p: leaves(evs) for p, evs in r.trace.devices.items()})
            secs = layers.layer_seconds(tr, *found)
            if set(secs) - {layers.UNATTRIBUTED}:
                ms = {k: 1e3 * v / steps for k, v in secs.items()}
        r._leaf_layer_ms = ms
    return r._leaf_layer_ms


def knn_ms(ms: dict) -> float | None:
    """The kNN head's ms: `knn_head` and the search's `search_*` layers
    nested in it."""
    parts = [v for k, v in ms.items() if k is not None
             and (k == "knn_head" or k.startswith("search_"))]
    return sum(parts) if parts else None
