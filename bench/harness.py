"""The data-driven harness: one run of one cell.

Everything a cell is made of is found by name, so a later change adds
files and entries and edits none:

* `BENCHMARK.json` (the spec) names the cell, its configuration, its chips,
  and which end-to-end and per-layer metrics it reports;
* `configs/<config>.json` holds the configuration's sizes (its path is the
  spec's `file`);
* `workloads/<cell>.json` holds the traffic mix: the driver that generates
  it and the driver's parameters;
* `drivers/<driver>.py` runs a traffic mix: `setup(ctx) -> state`,
  `window(ctx, state) -> dict`, `check(ctx, state) -> {name: (value,
  limit)}`;
* `metrics/<metric>.py` reads one per-layer metric from a traced window:
  `read(r) -> float | None` (None: nothing to read, the metric is left
  out of the line).

A run: set-up (data, weights, every compiled shape warmed; its host time
from process start is `setup_s`), the measured window, the device's peak
memory, then the comparison with the plain reference that decides
`correct`. With `trace` the window runs under the profiler and the line
carries the per-layer metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Any, Callable, Sequence

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SPEC_FILE = os.path.join(ROOT, "BENCHMARK.json")
OUT_DIR = os.path.join(ROOT, "bench_out")


def load_spec(path: str = SPEC_FILE) -> dict:
    with open(path) as f:
        return json.load(f)


def find(dirs: Sequence[str], sub: str, name: str, ext: str) -> str:
    """The first `<dir>/<sub>/<name><ext>` that exists."""
    for d in dirs:
        path = os.path.join(d, sub, name + ext)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {sub}/{name}{ext} under {list(dirs)}")


def load_module(path: str):
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_") \
        .replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    workload: dict
    driver: Any
    end_to_end: list            # spec entries this cell reports
    per_layer: list             # (spec entry, reader module)


def resolve(spec: dict, name: str, dirs: Sequence[str] = (BENCH_DIR,),
            root: str = ROOT) -> Cell:
    cells = [w for w in spec["workloads"] if w["name"] == name]
    if len(cells) != 1:
        raise KeyError(f"cell {name!r}: {len(cells)} entries in the spec")
    entry = cells[0]
    configs = [c for c in spec["configs"] if c["name"] == entry["config"]]
    if len(configs) != 1:
        raise KeyError(f"configuration {entry['config']!r}: {len(configs)} "
                       f"entries in the spec")
    with open(os.path.join(root, configs[0]["file"])) as f:
        config = json.load(f)
    with open(find(dirs, "workloads", name, ".json")) as f:
        workload = json.load(f)
    if workload["config"] != entry["config"]:
        raise ValueError(f"workloads/{name}.json runs {workload['config']!r}"
                         f", the spec says {entry['config']!r}")
    driver = load_module(find(dirs, "drivers", workload["driver"], ".py"))
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [(m, load_module(find(dirs, "metrics", m["name"], ".py")))
                 for m in spec["per_layer"] if name in m["workloads"]]
    return Cell(name, entry["chips"], config, workload, driver, e2e,
                per_layer)


class Context:
    """What a driver sees of the run: the cell's files, the seed, the
    window length, host timers, and the host spans that the trace
    reduction attributes device idle time to."""

    def __init__(self, cell: Cell, seed: int, seconds: float, devices):
        self.cell = cell
        self.config = cell.config
        self.workload = cell.workload
        self.chips = cell.chips
        self.seed = seed
        self.seconds = seconds
        self.devices = devices
        self.rng = np.random.default_rng(seed)
        self.timers: dict[str, float] = {}

    @staticmethod
    def span(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def timed(self, name: str):
        """Host time of the block, added to `timers[name]`, under the host
        span `bench.<name>`."""
        t0 = time.perf_counter()
        with self.span("bench." + name):
            yield
        self.timers[name] = self.timers.get(name, 0.0) \
            + time.perf_counter() - t0

    @staticmethod
    def log(msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader sees."""
    trace: Any                  # bench.trace.Trace of the window
    window: dict                # the driver's window result
    ctx: Context
    peak: dict                  # the chip's peaks (bench/peaks.json)


LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@contextlib.contextmanager
def count_lowerings():
    """Programs lowered inside the block (each new compiled program, found
    in the persistent cache or not): a warm window lowers none."""
    import jax
    found = []
    listen = lambda event, duration, **kw: (
        found.append(kw.get("fun_name")) if event == LOWERING_EVENT else None)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield found
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@contextlib.contextmanager
def time_collections():
    """Durations of the garbage collector's passes inside the block, in s."""
    passes, start = [], []

    def note(phase, info):
        if phase == "start":
            start[:] = [time.perf_counter()]
        elif start:
            passes.append(time.perf_counter() - start.pop())
    gc.callbacks.append(note)
    try:
        yield passes
    finally:
        gc.callbacks.remove(note)


def _memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(spec: dict, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, dirs: Sequence[str] = (BENCH_DIR,),
             root: str = ROOT, out_dir: str = OUT_DIR,
             peak_of: Callable[[str], dict] | None = None) -> dict:
    """One run of cell `name`; returns the result line (a dict)."""
    import jax

    from bench import trace as trace_lib
    from bench import work

    cell = resolve(spec, name, dirs, root)
    devices = jax.devices()[:cell.chips]
    kind = devices[0].device_kind
    peak = (peak_of or work.peaks)(kind)
    ctx = Context(cell, seed, seconds, devices)
    driver = cell.driver

    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    ctx.log(f"{name}: set-up {setup_s:.3f} s; window of {seconds} s, "
            f"trace {int(trace)}")
    trace_dir = os.path.join(out_dir, f"trace-{name}")
    with (trace_lib.capture(trace_dir) if trace
          else contextlib.nullcontext()), count_lowerings() as lowered, \
            time_collections() as collections:
        with ctx.span(trace_lib.WINDOW_SPAN):
            win = driver.window(ctx, state)
    ctx.log(f"{name}: programs compiled inside the window: {len(lowered)} "
            f"{sorted(set(lowered))}; garbage collections {len(collections)}"
            f", {1e3 * sum(collections):.1f} ms in all, longest "
            f"{1e3 * max(collections, default=0.0):.1f} ms")
    mem = _memory_peak(devices)
    t_check = time.perf_counter()
    checks = driver.check(ctx, state)
    ctx.log(f"{name}: check took {time.perf_counter() - t_check:.3f} s")
    correct = all(v <= lim for v, lim in checks.values())

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    line: dict[str, Any] = {"correct": correct,
                            "attempted": win["attempted"],
                            "failed": win["failed"]}
    if trace:
        tr = trace_lib.reduce(trace_lib.load_xplane(trace_dir), cell.chips)
        reading = Reading(tr, win, ctx, peak)
        metrics = {}
        for entry, reader in cell.per_layer:
            value = reader.read(reading)
            if value is not None:
                metrics[entry["name"]] = {"value": float(value),
                                          "unit": entry["unit"]}
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        line["metrics"] = metrics
        line["device"] = device
        line["breakdown"] = tr.breakdown()
    else:
        values = dict(win["metrics"], setup_s=setup_s)
        line["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                       "unit": m["unit"]}
                           for m in cell.end_to_end}
        line["device"] = device
    line["check"] = {k: {"value": float(v), "limit": float(lim)}
                     for k, (v, lim) in checks.items()}
    return line


def emit(line: dict) -> None:
    """The compared numbers as the last lines on stderr, then the result
    as the last line on stdout."""
    for k, c in line["check"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {k} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
