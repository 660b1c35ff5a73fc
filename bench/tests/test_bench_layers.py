"""Device time per layer of the program: the attribution of a trace's ops
to layers (bench/layers.py) and the readers built on it."""

import dataclasses
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, layers  # noqa: E402
from bench import trace as T  # noqa: E402

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
EXCERPT = os.path.join(HERE, "data", "trace_1536K_layers.json")
CELL = "omniglot-1536K.two_phase"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def hand_trace():
    """Window [0, 1000) ns. Chip 0 runs module `jit_a(1)` over [0, 500):
    a shortlist op [0, 300), a copy [300, 350) the table puts in the
    rescore, an op the table does not know [350, 400); then `jit_b(2)`,
    which has no table, [600, 700). Chip 1 runs `jit_a(1)`'s shortlist op
    [0, 200)."""
    op = lambda plane, name, s, d: T.Event(plane, T.OPS_LINE, name, s, d, {})
    mod = lambda plane, name, s, d: T.Event(plane, layers.MODULES_LINE,
                                            name, s, d, {})
    return [
        T.Event("/host:CPU", "python", T.WINDOW_SPAN, 0, 1000, {}),
        mod(DEV0, "jit_a(1)", 0, 500), mod(DEV0, "jit_b(2)", 600, 100),
        mod(DEV1, "jit_a(1)", 0, 200),
        op(DEV0, "%custom-call.1 = f32[8] custom-call()", 0, 300),
        op(DEV0, "%copy.2 = s8[8] copy(s8[8] %p)", 300, 50),
        op(DEV0, "%fusion.9 = f32[8] fusion()", 350, 50),
        op(DEV0, "%fusion.1 = f32[8] fusion()", 600, 100),
        op(DEV1, "custom-call.1", 0, 200),
    ]


TABLE = {"jit_a(1)": {"custom-call.1": "search_shortlist",
                      "copy.2": "search_rescore", "fusion.1": None}}


def test_ops_are_attributed_by_module_then_instruction():
    events = hand_trace()
    tr = T.reduce(events)
    modules = [e for e in events if e.line == layers.MODULES_LINE]
    secs = layers.layer_seconds(tr, modules, TABLE)
    # per chip, averaged over the two: shortlist (300 + 200) / 2; the copy
    # 50 / 2; the unknown op and the module without a table unattributed
    assert secs == pytest.approx({"search_shortlist": 250e-9,
                                  "search_rescore": 25e-9,
                                  layers.UNATTRIBUTED: 75e-9})
    assert sum(secs.values()) == pytest.approx(
        sum(e.dur_ns for e in events if e.line == T.OPS_LINE) / 2 / 1e9)
    assert layers.instruction("%copy.40 = s32[8] copy(s32[8] %x)") == \
        "copy.40"
    assert layers.instruction("wrapped_sine") == "wrapped_sine"


@dataclasses.dataclass
class Ctx:
    config: dict
    workload: dict
    chips: int = 1
    cell: object = dataclasses.field(
        default_factory=lambda: harness.resolve(harness.load_spec(), CELL))

    @staticmethod
    def log(msg):
        print(msg)


class Reading:
    def __init__(self, trace, batches):
        with open(os.path.join(ROOT, "bench", "configs",
                               "omniglot-conv4-1536K.json")) as f:
            config = json.load(f)
        with open(os.path.join(ROOT, "bench", "workloads",
                               CELL + ".json")) as f:
            workload = json.load(f)
        self.trace = trace
        self.window = {"counts": {"batches": batches}}
        self.ctx = Ctx(config, workload)
        self.peak = PEAK


def readers():
    spec = harness.load_spec()
    cell = harness.resolve(spec, CELL)
    return {e["name"]: mod for e, mod in cell.per_layer}


@pytest.fixture(scope="module")
def excerpt():
    """Two two_phase batches of a 1,572,864-row store on one v5e chip,
    with the layer table the profile carried, measured on one v5e chip: the events
    reduce as the harness reduces them, and `layers.load` is handed the
    excerpt's modules and table in place of the profile."""
    with open(EXCERPT) as f:
        data = json.load(f)
    events = [T.Event(*row) for row in data["events"]]
    modules = [e for e in events if e.line == layers.MODULES_LINE]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(layers, "load", lambda r: (modules, data["table"]))
        r = Reading(T.reduce(events, 1), 2)
        got = {name: mod.read(r) for name, mod in readers().items()
               if name.startswith(("phase", "shortlist", "unattributed"))}
    return r, got


def test_recorded_v5e_layers_cover_the_busy_time(excerpt):
    r, got = excerpt
    ms = layers.per_batch_ms(r)
    assert set(ms) <= {"search_quantize", "search_shortlist",
                       "search_rescore", "search_answer",
                       layers.UNATTRIBUTED}
    busy_ms = 1e3 * r.trace.busy_s / 2
    assert got["phase1_ms"] + got["phase2_ms"] + got["unattributed_ms"] \
        + ms["search_quantize"] + ms["search_answer"] == \
        pytest.approx(busy_ms, rel=0.01)
    assert got["unattributed_ms"] < 0.01 * busy_ms
    # the fused kernel is part of the phase-1 layer, and the relayout copy
    # of s_grid the rescore gathers from is part of phase 2
    assert got["phase1_ms"] >= got["shortlist_ms"]
    assert got["phase2_ms"] > 10.0
    assert 0 < got["phase1_roofline"] <= got["shortlist_roofline"]


def test_layer_readers_read_nothing_without_a_device_or_a_layer():
    events = [e for e in hand_trace() if not e.plane.startswith("/device")]
    r = Reading(T.reduce(events), 2)
    assert all(readers()[m].read(r) is None for m in
               ("phase1_ms", "phase2_ms", "phase1_roofline",
                "unattributed_ms"))
    # a program that names no layer: every op unattributed, nothing read
    r = Reading(T.reduce(hand_trace()), 2)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(layers, "load", lambda r: ([], {}))
        assert layers.per_batch_ms(r) is None
        assert readers()["unattributed_ms"].read(r) is None


def test_a_device_trace_without_hlo_is_an_error(tmp_path):
    run = tmp_path / f"trace-{CELL}" / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(b"")
    r = Reading(T.reduce(hand_trace(), 1), 2)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "OUT_DIR", str(tmp_path))
        with pytest.raises(RuntimeError, match="carries no HLO"):
            layers.per_batch_ms(r)


def test_a_program_without_the_layer_table_reads_nothing():
    """A checkout whose program predates `repro.analysis.profile` (as the
    benchmark may be laid over one): the readers leave their metrics out
    and raise nothing."""
    r = Reading(T.reduce(hand_trace(), 1), 2)
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(sys.modules, "repro.analysis.profile", None)
        assert all(readers()[m].read(r) is None for m in
                   ("phase1_ms", "phase2_ms", "phase1_roofline",
                    "unattributed_ms"))
