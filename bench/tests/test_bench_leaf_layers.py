"""Layer time from leaf ops only: a control-flow op that spans the body
ops running inside it is not counted on top of them."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import layers, leaf_layers  # noqa: E402
from bench import trace as T  # noqa: E402

DEV = "/device:TPU:0"


def op(name, s, d):
    return T.Event(DEV, T.OPS_LINE, name, s, d, {})


def test_containers_are_left_out_and_leaves_kept():
    # a while [0, 400) around two body ops [0, 100) and [150, 400), a
    # nested while [150, 400) around [150, 300); then a leaf [500, 600)
    # and two leaves that overlap without nesting [700, 800), [750, 900)
    evs = [op("%while.4 = () while()", 0, 400), op("%fusion.1 = f()", 0, 100),
           op("%while.5 = () while()", 150, 250),
           op("%fusion.2 = f()", 150, 150), op("%fusion.3 = f()", 500, 100),
           op("%copy.1 = c()", 700, 100), op("%copy.2 = c()", 750, 150)]
    kept = {layers.instruction(e.name) for e in leaf_layers.leaves(evs)}
    assert kept == {"fusion.1", "fusion.2", "fusion.3", "copy.1", "copy.2"}


def test_per_step_layer_time_counts_the_body_once(monkeypatch):
    window = T.Event("/host:CPU", "python", T.WINDOW_SPAN, 0, 1000, {})
    module = T.Event(DEV, layers.MODULES_LINE, "jit_step(1)", 0, 1000, {})
    evs = [window, module, op("%while.4 = () while()", 0, 600),
           op("%fusion.1 = f()", 0, 300), op("%fusion.2 = f()", 300, 300),
           op("%fusion.3 = f()", 700, 200)]
    table = {"jit_step(1)": {"while.4": "decoder", "fusion.1": "decoder",
                             "fusion.2": "decoder", "fusion.3": "knn_head"}}

    class Reading:
        trace = T.reduce(evs)
        window = {"counts": {"steps": 2}}
    monkeypatch.setattr(layers, "load", lambda r: ([module], table))
    ms = leaf_layers.per_step_ms(Reading())
    assert ms == pytest.approx({"decoder": 600e-6 / 2, "knn_head": 200e-6 / 2})
    assert leaf_layers.knn_ms(dict(ms, search_rescore=1.0)) \
        == pytest.approx(1.0 + 1e-4)
