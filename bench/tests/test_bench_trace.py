"""The trace reduction: idle share, scope and collective time, breakdown."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import trace as T  # noqa: E402

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def op(plane, name, start, dur, **stats):
    return T.Event(plane, T.OPS_LINE, name, start, dur, stats)


def host(name, start, dur):
    return T.Event("/host:CPU", "python", name, start, dur, {})


def hand_trace():
    """Window [100, 1100) ns. Chip 0: a shortlist op [100, 400), a fusion
    [350, 500) overlapping it, an all-gather [700, 800), an op past the
    window's end [1050, 1200). Chip 1: one op [100, 600). Host: a search
    span [100, 600), a readback span [600, 1000)."""
    return [
        host(T.WINDOW_SPAN, 100, 1000),
        host("bench.search", 100, 500),
        host("bench.readback", 600, 400),
        op(DEV0, "custom-call.1", 100, 300,
           tf_op="jit(search)/shortlist_fused/pallas_call"),
        op(DEV0, "fusion.2", 350, 150, tf_op="jit(search)/rescore"),
        op(DEV0, "all-gather.3", 700, 100, hlo_category="collective"),
        op(DEV0, "fusion.4", 1050, 150),
        op(DEV1, "fusion.5", 100, 500),
        op(DEV0, "outside", 0, 50),                        # before the window
        T.Event(DEV0, "XLA Modules", "jit_search", 100, 900, {}),
    ]


def test_busy_and_idle_share():
    tr = T.reduce(hand_trace())
    assert tr.window_s == pytest.approx(1000e-9)
    # chip 0 busy: [100, 500) + [700, 800) + [1050, 1100) = 550 ns;
    # chip 1: 500 ns; the mean over both chips
    assert tr.busy_s == pytest.approx(525e-9)
    assert tr.idle_share == pytest.approx(0.475)
    one = T.reduce(hand_trace(), n_devices=1)
    assert one.busy_s == pytest.approx(550e-9)


def test_scope_and_collective_seconds():
    tr = T.reduce(hand_trace())
    # 300 ns on chip 0, none on chip 1: averaged over the two chips
    assert tr.scope_seconds("shortlist_fused") == pytest.approx(150e-9)
    assert tr.collective_seconds() == pytest.approx(50e-9)
    assert tr.scope_seconds("no_such_scope") == 0.0


def test_breakdown_names_ops_and_idle_gaps_by_host_span():
    b = T.reduce(hand_trace(), n_devices=1).breakdown()
    ops = dict(b["device_ops"])
    assert ops["jit(search)/shortlist_fused/pallas_call"] == \
        pytest.approx(300e-9)
    assert ops["fusion.4"] == pytest.approx(50e-9)        # clipped
    assert "outside" not in ops
    gaps = {k.split(" ")[0]: v for k, v in b["idle_gaps"]}
    # chip 0 idles [500, 700) and [800, 1050); at both middles (600, 925)
    # the innermost open host span is the readback
    assert gaps == pytest.approx({"bench.readback": 200e-9 + 250e-9})
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_one_window_span_is_required():
    events = [e for e in hand_trace() if e.name != T.WINDOW_SPAN]
    with pytest.raises(RuntimeError):
        T.reduce(events)


def test_recorded_profile_round_trips(tmp_path):
    """A real profiler recording (of this host; the CPU has no device
    plane): the window and host spans are found, and saved and loaded
    events reduce to the same numbers."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with T.capture(str(tmp_path / "prof")):
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.search"):
                    f(x).block_until_ready()
    events = T.load_xplane(str(tmp_path / "prof"))
    tr = T.reduce(events)
    assert tr.window_s > 0 and tr.busy_s == 0.0 and not tr.devices
    assert [h.name for h in tr.host_spans] == ["bench.search"] * 3
    copy = tmp_path / "events.json"
    T.save_events(events, str(copy))
    again = T.reduce(T.load_events(str(copy)))
    assert again.window_s == tr.window_s
    assert again.breakdown() == tr.breakdown()


def test_recorded_v5e_trace(tmp_path):
    """An excerpt of a v5e trace of two_phase search over a 1,048,576-row
    store (two batches, measured on one v5e chip): the scope, busy and idle
    numbers it reduces to, and a save/load round trip."""
    events = T.load_events(os.path.join(HERE, "data",
                                        "trace_1M_two_phase.json"))
    tr = T.reduce(events, n_devices=1)
    assert list(tr.devices) == ["/device:TPU:0"]
    assert tr.busy_s == pytest.approx(0.224126066)
    assert tr.window_s == pytest.approx(0.227616035)
    assert tr.scope_seconds("shortlist_fused") == pytest.approx(0.198826606)
    gaps = dict(tr.breakdown()["idle_gaps"])
    assert max(gaps, key=gaps.get).startswith("bench.readback")
    copy = tmp_path / "events.json"
    T.save_events(events, str(copy))
    assert T.reduce(T.load_events(str(copy)), 1).breakdown() == tr.breakdown()
