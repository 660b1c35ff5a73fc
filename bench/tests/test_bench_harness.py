"""The harness is driven by data: a cell and a per-layer metric that exist
only as new files (here in a temporary directory) and new spec entries are
found by name and run at toy size through the same driver code, and the
result line has the keys the contract names."""

import copy
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

CELL = "toy-omniglot.two_phase"
FAKE_PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
METRIC = '''"""Batches answered in the window (a toy per-layer reader)."""


def read(r):
    return r.window["counts"]["batches"]
'''


def toy_cell(tmp):
    """A toy-sized twin of the retrieval cell, as new files and new
    entries only."""
    for sub in ("configs", "workloads", "metrics"):
        os.makedirs(os.path.join(tmp, sub))
    with open(os.path.join(ROOT, "bench/configs/omniglot-conv4-1536K.json")) as f:
        cfg = json.load(f)
    cfg.update(name="toy-omniglot", rows=2048, write_batch=512)
    cfg_path = os.path.join(tmp, "configs", "toy-omniglot.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(ROOT,
                           "bench/workloads/omniglot-1536K.two_phase.json")) as f:
        wl = json.load(f)
    wl.update(config="toy-omniglot", batch=8, query_pool_batches=4,
              check_batches=2, check_rows=256)
    with open(os.path.join(tmp, "workloads", CELL + ".json"), "w") as f:
        json.dump(wl, f)
    with open(os.path.join(tmp, "metrics", "toy_batches.py"), "w") as f:
        f.write(METRIC)
    spec = copy.deepcopy(harness.load_spec())
    spec["configs"].append({"name": "toy-omniglot", "source": "toy",
                            "file": cfg_path, "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": CELL, "config": "toy-omniglot",
                              "traffic": "toy", "chips": 1, "why": "toy"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("qps", "search_p95_ms", "shortlist_ms",
                         "device_idle.search"):
            m["workloads"].append(CELL)
    spec["per_layer"].append({"name": "toy_batches", "unit": "batches",
                              "better": "higher", "source": "host_clock",
                              "layer": "search engine", "moves": "qps",
                              "workloads": [CELL]})
    return spec


def run(spec, tmp, trace):
    return harness.run_cell(spec, CELL, 2 ** 31 + 7, 1.0, trace,
                            time.perf_counter(), dirs=[tmp,
                                                       harness.BENCH_DIR],
                            out_dir=os.path.join(tmp, "out"),
                            peak_of=lambda kind: FAKE_PEAK)


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("toy"))
    return toy_cell(tmp), tmp


def test_new_files_are_found_by_name_and_run(toy):
    spec, tmp = toy
    line = run(spec, tmp, trace=False)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "check"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 8 == 0
    assert set(line["metrics"]) == {"qps", "search_p95_ms", "setup_s"}
    assert line["metrics"]["qps"]["unit"] == "queries/s"
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
    assert all(c["value"] <= c["limit"] for c in line["check"].values())


def test_traced_run_reports_the_new_per_layer_metric(toy):
    spec, tmp = toy
    line = run(spec, tmp, trace=True)
    assert list(line)[-1] == "check" and "breakdown" in line
    assert line["metrics"]["toy_batches"]["value"] > 0
    assert line["metrics"]["toy_batches"]["unit"] == "batches"
    # no device plane on the CPU: the device readers find nothing to read,
    # and the line leaves their metrics out
    assert "shortlist_ms" not in line["metrics"]
    assert "device_idle.search" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_unknown_cell_and_missing_files_are_errors(toy, tmp_path):
    spec, tmp = toy
    with pytest.raises(KeyError):
        harness.resolve(spec, "no-such-cell", [tmp, harness.BENCH_DIR])
    with pytest.raises(FileNotFoundError):
        harness.resolve(spec, CELL, [str(tmp_path)])


def test_per_layer_metric_without_its_cells_is_an_error(toy):
    spec, tmp = toy
    spec = copy.deepcopy(spec)
    del spec["per_layer"][-1]["workloads"]
    with pytest.raises(KeyError):
        harness.resolve(spec, CELL, [tmp, harness.BENCH_DIR])
