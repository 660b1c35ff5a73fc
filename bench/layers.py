"""Device time per batch in each layer of the program, from a traced window.

The program names its layers with `jax.named_scope` (`search_quantize`,
`search_shortlist`, `search_rescore`, `search_answer`, ...: the
`LAYER_SCOPES` of `repro.analysis.hlo_contracts`). The profile the harness
wrote for the window (`<OUT_DIR>/trace-<cell>`) carries the optimized HLO of
every program the process compiled, and `repro.analysis.profile
.profile_layers` maps each of their instructions to its layer. Here each
`XLA Ops` event of a chip the cell uses is attributed to the module running
around it on that chip's `XLA Modules` line, then to a layer by its
instruction name, and its time inside the window is summed per layer,
averaged over the chips. Ops that no table names, or that no layer scope
covers, fall under `UNATTRIBUTED`.

A program that names no layers has no such table (`repro.analysis.profile`
is missing, or no instruction maps to a layer): the readers then find
nothing to read. A device trace whose profile carries no HLO is an error.
"""

from __future__ import annotations

import bisect
import importlib
import os
import re
import time

from bench import harness
from bench import trace as trace_lib

UNATTRIBUTED = None
MODULES_LINE = "XLA Modules"
# a TPU op event is named by its HLO text (`%copy.40 = s32[...] copy(...)`),
# a CPU one by the instruction alone
_INSTRUCTION = re.compile(r"^%?([^\s=]+)")


def instruction(event_name: str) -> str:
    return _INSTRUCTION.match(event_name).group(1)


def layer_seconds(tr: trace_lib.Trace, modules: list[trace_lib.Event],
                  table: dict[str, dict[str, str | None]]
                  ) -> dict[str | None, float]:
    """{layer: device seconds in the window}, per chip (mean over the
    chips of `tr`). `modules`: the `XLA Modules` events of the trace."""
    out: dict[str | None, float] = {}
    for plane, ops in tr.devices.items():
        mods = sorted((m for m in modules if m.plane == plane),
                      key=lambda m: m.start_ns)
        starts = [m.start_ns for m in mods]
        for e in ops:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            ops_of = (table.get(mods[i].name, {})
                      if i >= 0 and e.start_ns < mods[i].end_ns else {})
            layer = ops_of.get(instruction(e.name), UNATTRIBUTED)
            out[layer] = out.get(layer, 0.0) + max(
                0.0, min(e.end_ns, tr.end_ns) - max(e.start_ns, tr.start_ns))
    n = max(1, len(tr.devices))
    return {k: v / n / 1e9 for k, v in out.items()}


def load(r) -> tuple[list[trace_lib.Event], dict] | None:
    """The window's `XLA Modules` events and the layer table of the
    profile the harness wrote; None for a program that names no layers."""
    try:
        profile = importlib.import_module("repro.analysis.profile")
    except ModuleNotFoundError as e:
        if e.name != "repro.analysis.profile":
            raise
        return None
    log_dir = os.path.join(harness.OUT_DIR, f"trace-{r.ctx.cell.name}")
    t0 = time.perf_counter()
    table = profile.profile_layers(log_dir)
    if not table:
        raise RuntimeError(f"the device trace under {log_dir} carries no "
                           f"HLO to map its ops to layers")
    modules = [e for e in trace_lib.load_xplane(log_dir)
               if e.line == MODULES_LINE]
    r.ctx.log(f"layers: {len(table)} module table(s) and {len(modules)} "
              f"module runs read in {time.perf_counter() - t0:.3f} s; "
              f"profile {os.path.getsize(profile.xplane_path(log_dir))} B")
    return modules, table


def per_batch_ms(r) -> dict[str | None, float] | None:
    """{layer: device ms per batch} of the traced window (cached on the
    reading); None where there is nothing to read."""
    if "_layer_ms" not in vars(r):
        c = r.window["counts"]
        found = (load(r) if c.get("batches") and r.trace.devices else None)
        ms = None
        if found is not None:
            secs = layer_seconds(r.trace, *found)
            if set(secs) - {UNATTRIBUTED}:
                ms = {k: 1e3 * v / c["batches"] for k, v in secs.items()}
        r._layer_ms = ms
    return r._layer_ms
