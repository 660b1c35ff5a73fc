"""The kNN-LM decode cell at toy size through the harness: a sound run
reads `correct` true, and planted faults under the program's normal entry
points read it false -- a decoder that ignores the sliding window, a
serve step that searches with a truncated query, datastore keys taken
before the final norm, a fault in one batch row's cache, a datastore row
with the wrong label. The control readings close the file."""

import copy
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


from bench import control, harness  # noqa: E402

REAL, CONFIG = "starcoder2-3b-knnlm.decode", "starcoder2-3b-knnlm"
CELL = "toy.decode"
FAKE_PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
DECODER = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
               d_ff=128, vocab_size=256, window=16,
               max_position_embeddings=40)
BATCH = 4


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("decode"))
    spec = copy.deepcopy(harness.load_spec())
    for sub in ("configs", "workloads"):
        os.makedirs(os.path.join(tmp, sub))
    with open(os.path.join(ROOT, "bench/configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg["decoder"].update(DECODER)
    cfg["dim"] = DECODER["d_model"]
    cfg.update(name="toy-decode", rows=512, prefill_chunk=128,
               write_batch=64, knn=dict(cfg["knn"], k=8))
    path = os.path.join(tmp, "configs", "toy-decode.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(ROOT, "bench/workloads", REAL + ".json")) as f:
        wl = json.load(f)
    wl.update(config="toy-decode", batch=BATCH, prompt_len=24, check_steps=4,
              check_rows=128)
    with open(os.path.join(tmp, "workloads", CELL + ".json"), "w") as f:
        json.dump(wl, f)
    spec["configs"].append({"name": "toy-decode", "source": "toy",
                            "file": path, "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": CELL, "config": "toy-decode",
                              "traffic": "toy", "chips": 1, "why": "toy"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    return spec, tmp


def run(toy, seed=2 ** 31 + 11, trace=False):
    spec, tmp = toy
    return harness.run_cell(spec, CELL, seed, 0.5, trace,
                            time.perf_counter(),
                            dirs=[tmp, harness.BENCH_DIR],
                            out_dir=os.path.join(tmp, "out"),
                            peak_of=lambda kind: FAKE_PEAK)


def failed(line):
    return [k for k, c in line["check"].items() if c["value"] > c["limit"]]


def test_sound_run_is_correct(toy):
    line = run(toy)
    assert line["correct"] is True, line["check"]
    assert set(line["metrics"]) == {"qps", "search_p95_ms", "setup_s"}
    assert line["attempted"] > 0 and line["attempted"] % BATCH == 0


def test_traced_run_reads_the_host_clock_metrics(toy):
    line = run(toy, trace=True)
    # no device plane on the CPU: only the host-clock readers have a number
    assert set(line["metrics"]) == {"decode_mfu", "store_write_s"}
    assert 0 < line["metrics"]["decode_mfu"]["value"]
    assert 0 < line["metrics"]["store_write_s"]["value"]


def test_decoder_that_ignores_the_window(toy, monkeypatch):
    from repro.configs.base import ModelConfig
    types = ModelConfig.layer_types
    monkeypatch.setattr(ModelConfig, "layer_types", lambda self: tuple(
        "attn" if t == "swa" else t for t in types(self)))
    line = run(toy)
    assert "lm_logprob_err" in failed(line)


def test_search_on_a_truncated_query(toy, monkeypatch):
    """The serve step's hidden state cut to its first 8 units before it is
    recorded and searched: the search agrees with the reference on that
    query, the query itself does not."""
    from repro.models import transformer as tfm
    decode = tfm.decode_step

    def truncated(*a, **kw):
        logits, caches, h = decode(*a, **kw)
        return logits, caches, h.at[..., 8:].set(0)
    monkeypatch.setattr(tfm, "decode_step", truncated)
    line = run(toy)
    assert "query_err" in failed(line)
    assert line["check"]["lm_logprob_err"]["value"] <= \
        line["check"]["lm_logprob_err"]["limit"]


def test_keys_taken_before_the_final_norm(toy, monkeypatch):
    """Datastore keys that are not the vector the LM head reads: the store
    holds what the reference quantizes of the program's keys, the keys
    themselves differ from the reference's forward."""
    from repro.models import transformer as tfm
    prefill = tfm.prefill

    def pre_norm(params, cfg, batch, max_seq, rules=None):
        logits, h, caches = prefill(params, cfg, batch, max_seq, rules)
        return logits, 2.0 * h - 1.0, caches
    monkeypatch.setattr(tfm, "prefill", pre_norm)
    line = run(toy)
    assert failed(line) == ["key_err"]


def test_one_batch_row_fault(toy, monkeypatch):
    """The last sequence's ring is prefilled from its prompt rolled by one
    token: only that row decodes from a context other than its own, and
    the check reads every row."""
    import jax.numpy as jnp

    from repro.launch import steps as steps_lib
    make = steps_lib.make_prefill_into_step

    def faulty(cfg, rules, max_seq):
        step = make(cfg, rules, max_seq)

        def into(params, caches, tokens, row):
            if int(row) == BATCH - 1:
                tokens = jnp.roll(tokens, 1, axis=1)
            return step(params, caches, tokens, row)
        return into
    monkeypatch.setattr(steps_lib, "make_prefill_into_step", faulty)
    line = run(toy)
    assert "lm_logprob_err" in failed(line)


def test_datastore_row_with_a_wrong_label(toy, monkeypatch):
    from repro.engine import MemoryStore
    write = MemoryStore.write
    monkeypatch.setattr(MemoryStore, "write", lambda self, v, lab: write(
        self, v, lab.at[0].add(1)))
    line = run(toy)
    assert "store_mismatch" in failed(line) or \
        "shortlist_mismatch" in failed(line)


def test_control_readings_order(toy):
    spec, tmp = toy
    out = control.readings(spec, CELL, 7, 0.5,
                           dirs=[tmp, harness.BENCH_DIR])
    prog = out["program"]
    for name in ("float8_weights", "no_window"):
        for k in ("lm_logprob_err", "query_err", "key_err"):
            assert prog[k] < out[name][k], (name, k)
    assert out["bfloat16_knn"]["store_mismatch"] > 0
