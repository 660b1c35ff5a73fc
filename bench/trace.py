"""Profiler capture and the reduction from a device trace to metrics.

A traced run records the measured window with the JAX profiler (the Python
tracer off, so the host is not slowed by it) and reduces the trace here:

* the window is the host span `bench.window`; every device interval is
  clipped to it;
* busy time of a chip is the union of its operations' intervals, averaged
  over the chips the cell uses; idle share = 1 - busy / window;
* the time of a scope (`shortlist_fused`) or of the collectives is the sum
  of the durations of the matching operations, averaged over the chips;
* the breakdown lists the operations that took most device time and the
  device's idle time, grouped by the innermost `bench.` host span that was
  open in the middle of each gap: what the host was doing meanwhile.

Everything past `load_xplane` works on plain `Event` records, so a small
recorded trace (bench/tests/) exercises the same code.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import json
import os
import re
import shutil

WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def text(self) -> str:
        """Name and string stats, where scopes and categories live."""
        return " ".join([self.name] + [v for v in self.stats.values()
                                       if isinstance(v, str)])


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the enclosed block into `log_dir` (replaced)."""
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load_xplane(log_dir: str) -> list[Event]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{paths}")
    events = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for e in line.events:
                stats = {k: v for k, v in e.stats
                         if isinstance(v, (str, int, float))}
                events.append(Event(plane.name, line.name, e.name,
                                    float(e.start_ns), float(e.duration_ns),
                                    stats))
    return events


def save_events(events: list[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_events(path: str) -> list[Event]:
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Trace:
    """A reduced trace of one window."""
    window_s: float
    busy_s: float                      # mean over the chips used
    devices: dict                      # plane -> [Event] inside the window
    host_spans: list                   # bench.* host Events in the window
    start_ns: float
    end_ns: float

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, match) -> float:
        """Device seconds of the operations `match(event)` accepts, summed
        per chip and averaged over the chips."""
        if not self.devices:
            return 0.0
        total = sum(self._clipped(e) for evs in self.devices.values()
                    for e in evs if match(e))
        return total / len(self.devices) / 1e9

    def scope_seconds(self, scope: str) -> float:
        return self.op_seconds(lambda e: scope in e.text())

    def collective_seconds(self) -> float:
        return self.op_seconds(lambda e: COLLECTIVE.search(e.text())
                               is not None)

    def _clipped(self, e: Event) -> float:
        return max(0.0, min(e.end_ns, self.end_ns)
                   - max(e.start_ns, self.start_ns))

    def breakdown(self, top: int = 10) -> dict:
        ops: dict[str, float] = {}
        for evs in self.devices.values():
            for e in evs:
                key = e.stats.get("tf_op") or e.name
                ops[key] = ops.get(key, 0.0) + self._clipped(e)
        n = max(1, len(self.devices))
        device_ops = sorted(((k, v / n / 1e9) for k, v in ops.items()),
                            key=lambda kv: -kv[1])[:top]
        gaps: dict[str, list[float]] = {}
        for evs in self.devices.values():
            busy = _union([(max(e.start_ns, self.start_ns),
                            min(e.end_ns, self.end_ns)) for e in evs])
            edges = [self.start_ns] + [x for iv in busy for x in iv] \
                + [self.end_ns]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    label = self._host_span_at((s + e) / 2)
                    gaps.setdefault(label, []).append((e - s) / n / 1e9)
        idle = sorted(((f"{k} x{len(v)} (longest {max(v) * 1e3:.3f} ms)",
                        sum(v)) for k, v in gaps.items()),
                      key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in device_ops],
                "idle_gaps": [[k, v] for k, v in idle]}

    def _host_span_at(self, t: float, depth: int = 4) -> str:
        """Innermost host span open at t. Spans are sorted by start and
        nest at most `depth` deep, so only the last few that start before
        t can contain it."""
        i = bisect.bisect_right(self._starts, t)
        inner = None
        for h in self.host_spans[max(0, i - depth):i]:
            if t <= h.end_ns and (inner is None or h.dur_ns < inner.dur_ns):
                inner = h
        return inner.name if inner is not None else WINDOW_SPAN

    def __post_init__(self) -> None:
        self.host_spans = sorted(self.host_spans, key=lambda h: h.start_ns)
        self._starts = [h.start_ns for h in self.host_spans]


def reduce(events: list[Event], n_devices: int | None = None) -> Trace:
    """Reduce one traced window. `n_devices`: the chips the cell uses (the
    first ones by id); None takes every device plane."""
    wins = [e for e in events if e.name == WINDOW_SPAN]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} span, found "
                           f"{len(wins)}")
    ws, we = wins[0].start_ns, wins[0].end_ns
    planes = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)},
                    key=lambda p: int(p.rsplit(":", 1)[1]))
    if n_devices is not None:
        planes = planes[:n_devices]
    devices = {p: [e for e in events if e.plane == p and e.line == OPS_LINE
                   and e.dur_ns > 0 and e.end_ns > ws and e.start_ns < we]
               for p in planes}
    busy = [sum(e - s for s, e in _union(
        [(max(x.start_ns, ws), min(x.end_ns, we)) for x in evs]))
        for evs in devices.values()]
    host = [e for e in events if e.name.startswith(HOST_SPAN_PREFIX)
            and e.name != WINDOW_SPAN and not DEVICE_PLANE.match(e.plane)
            and e.end_ns > ws and e.start_ns < we]
    return Trace(window_s=(we - ws) / 1e9,
                 busy_s=(sum(busy) / len(busy) / 1e9) if busy else 0.0,
                 devices=devices, host_spans=host, start_ns=ws, end_ns=we)
