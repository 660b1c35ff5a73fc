"""The check catches a broken timed path. Each test skips the harness's
look for a chip, plants one fault under the program's normal entry points
and drives the rest of a run at toy size: `correct` must come out false.
Faults: a step that returns its state unchanged, half of the batch left
out (the rest's results standing in for it), an answer or votes altered
where they are produced. The control readings at toy size close the file."""

import copy
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import control, harness  # noqa: E402

FAKE_PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
TOYS = {
    # cell: (real cell, config file, config changes, workload changes)
    "toy.two_phase": ("omniglot-1536K.two_phase", "omniglot-conv4-1536K",
                      dict(rows=2048, write_batch=512),
                      dict(batch=8, query_pool_batches=4, check_batches=4,
                           check_rows=256)),
}


@pytest.fixture(scope="module")
def toys(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("toys"))
    spec = copy.deepcopy(harness.load_spec())
    for sub in ("configs", "workloads"):
        os.makedirs(os.path.join(tmp, sub))
    for cell, (real, config, cfg_over, wl_over) in TOYS.items():
        with open(os.path.join(ROOT, "bench/configs", config + ".json")) as f:
            cfg = json.load(f)
        cfg.update(cfg_over, name="toy-" + config)
        path = os.path.join(tmp, "configs", f"toy-{config}.json")
        with open(path, "w") as f:
            json.dump(cfg, f)
        with open(os.path.join(ROOT, "bench/workloads", real + ".json")) as f:
            wl = json.load(f)
        wl.update(wl_over, config="toy-" + config)
        with open(os.path.join(tmp, "workloads", cell + ".json"), "w") as f:
            json.dump(wl, f)
        spec["configs"].append({"name": "toy-" + config, "source": "toy",
                                "file": path, "reduced": [], "why": "toy"})
        spec["workloads"].append({"name": cell, "config": "toy-" + config,
                                  "traffic": "toy", "chips": 1,
                                  "why": "toy"})
        for m in spec["end_to_end"]:
            if real in m.get("workloads", []):
                m["workloads"].append(cell)
    return spec, tmp


def run(toys, cell, seed=5):
    spec, tmp = toys
    return harness.run_cell(spec, cell, seed, 0.5, False,
                            time.perf_counter(),
                            dirs=[tmp, harness.BENCH_DIR],
                            out_dir=os.path.join(tmp, "out"),
                            peak_of=lambda kind: FAKE_PEAK)


def failed(line):
    return [k for k, c in line["check"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("cell", list(TOYS))
def test_sound_run_is_correct(toys, cell):
    line = run(toys, cell)
    assert line["correct"] is True, line["check"]


# -- retrieval ---------------------------------------------------------------

def _wrap_search(monkeypatch, alter):
    from repro.engine import RetrievalEngine
    search = RetrievalEngine.search
    monkeypatch.setattr(RetrievalEngine, "search",
                        lambda self, st, q, req=None:
                        alter(search, self, st, q, req))


def test_store_write_that_keeps_the_state(toys, monkeypatch):
    from repro.engine import MemoryStore
    monkeypatch.setattr(MemoryStore, "write", lambda self, v, lab: self)
    line = run(toys, "toy.two_phase")
    assert line["correct"] is False and "store_mismatch" in failed(line)


def test_search_that_answers_half_the_batch(toys, monkeypatch):
    def half(search, eng, st, q, req):
        res = search(eng, st, q[:q.shape[0] // 2], req)
        return jax.tree_util.tree_map(lambda a: jnp.concatenate([a, a]), res)
    _wrap_search(monkeypatch, half)
    line = run(toys, "toy.two_phase")
    assert line["correct"] is False


def test_search_that_alters_an_answer(toys, monkeypatch):
    from repro.engine.api import SearchResult
    predict = SearchResult.predict
    monkeypatch.setattr(SearchResult, "predict",
                        lambda self: predict(self).at[0].add(1))
    line = run(toys, "toy.two_phase")
    assert failed(line) == ["answer_mismatch"]


def test_search_that_alters_votes(toys, monkeypatch):
    def alter(search, eng, st, q, req):
        res = search(eng, st, q, req)
        return res.__class__(res.votes.at[:, 0].add(1.0), res.dist,
                             res.indices, res.labels, res.iterations)
    _wrap_search(monkeypatch, alter)
    line = run(toys, "toy.two_phase")
    assert "vote_mismatch_pct" in failed(line)


# -- the control ---------------------------------------------------------------

@pytest.mark.parametrize("cell", list(TOYS))
def test_control_fails_a_limit(toys, cell):
    spec, tmp = toys
    out = control.readings(spec, cell, 7, 0.5,
                           dirs=[tmp, harness.BENCH_DIR])
    limits = harness.resolve(spec, cell, [tmp, harness.BENCH_DIR]) \
        .driver.LIMITS
    compared = lambda readings: {k: v for k, v in readings.items()
                                 if k in limits}
    assert all(v <= limits[k] for k, v in compared(out["program"]).items())
    assert any(v > limits[k] for k, v in compared(out["control"]).items())


@pytest.mark.parametrize("cell", list(TOYS))
def test_reordered_reference_is_within_the_limits(toys, cell):
    spec, tmp = toys
    out = control.readings(spec, cell, 9, 0.5,
                           dirs=[tmp, harness.BENCH_DIR])
    limits = harness.resolve(spec, cell, [tmp, harness.BENCH_DIR]) \
        .driver.LIMITS
    assert all(out["reordered"][k] <= lim for k, lim in limits.items())
