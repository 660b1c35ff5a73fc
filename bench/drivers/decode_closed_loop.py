"""Closed-loop kNN-LM decode: StarCoder2 with an MCAM datastore.

Set-up, through the program's serving entry points (`repro.launch.serve`,
`repro.launch.steps`): seeded random weights; a seeded token stream
standing for the repository, prefilled chunk by chunk, whose post-final-
norm hidden states become the datastore's keys (`serve.datastore_keys`),
each labelled with the next token and programmed into the store
(`serve.build_datastore`: calibrated on the first write batch, written by
`MemoryStore.write`); then a session: `batch` prompts,
seeded slices of the stream, each prefilled into its row of the decode
cache (`steps.make_prefill_into_step`), the first token the prefill's
greedy choice. One decode step is run to warm its program (it writes the
same slot the window's first step writes again, with the same values).

The window: one client, closed loop. Every step decodes one token for
every sequence (`steps.make_serve_step_with_mcam`: the decoder, then one
store search per sequence and the mixture; the caches donated) and the
batch's tokens are read back to the host before the next step is issued.
All sequences share one position, the step's scalar `pos`. A session
that would pass the decoder's last position starts a new one inside the
window: new prompts, prefilled again.

The check, once the window has closed, compares what the timed steps
produced, kept on the device during the window for `check_steps` steps
drawn from the seed across the whole window (reservoir sampling), for
every sequence of the batch, with `bench.reference.starcoder2`:

* `lm_logprob_err`: the largest KL(reference || program), in nats, of the
  program's LM log-probs at a sampled step, the reference's float32
  forward running over the sequence's actual tokens (prompt and what the
  program chose);
* `query_err`: the largest relative L2 gap between the vector the program
  searched with and the reference's post-final-norm hidden state at the
  same position of the same forward;
* `key_err`: the same gap between the program's datastore keys over one
  stream chunk drawn from the seed and the reference's forward over that
  chunk;
* `store_mismatch`: sampled stored rows (quantized values, programmed
  cells, labels) against the reference's quantization and layout of the
  program's own keys;
* `shortlist_mismatch`, `vote_mismatch_pct`: every query of the sampled
  steps, the reference search run on the vector the program searched with;
* `mix_err`: the largest gap, in nats, between the served log-probs and
  the reference mixture of the program's own LM log-probs and votes;
* `token_mismatch`: served tokens that are not the argmax of the served
  mixture.

Splitting the check at the query keeps each half off the other's
rounding: the 4-level query quantization may send a bfloat16 decoder and
a float32 reference to different neighbours, and those to different
tokens. The vectors themselves, queries and keys, are held to the
reference by a float tolerance. Limits: PERF.md, section 2.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers.retrieval_closed_loop import search_config
from bench.reference import avss as ref_avss
from bench.reference import starcoder2 as ref
# what the parent of this cell's program lacks: it fails here, at once
from repro.launch.serve import (build_datastore, datastore_config,
                                datastore_keys)
from repro.launch.steps import (make_prefill_into_step,
                                make_serve_step_with_mcam)

# the decoder block's keys that size the program's model
WIDTHS = {"n_layers": "n_layers", "d_model": "d_model", "n_heads": "n_heads",
          "n_kv_heads": "n_kv_heads", "head_dim": "head_dim", "d_ff": "d_ff",
          "vocab_size": "vocab_size", "window": "window",
          "norm_eps": "norm_eps", "rope_theta": "rope_theta",
          "dtype": "dtype"}
CHECKED = ("lm_logprob_err", "query_err", "key_err", "store_mismatch",
           "shortlist_mismatch", "vote_mismatch_pct", "mix_err",
           "token_mismatch")


def model_config(dec: dict):
    """The program's ModelConfig of the configuration's decoder: the
    architecture's own, at the widths the file states."""
    from repro.configs import load_config
    cfg = load_config(dec["arch"])
    over = {field: dec[key] for key, field in WIDTHS.items()}
    over["param_dtype"] = dec["dtype"]
    cfg = dataclasses.replace(cfg, remat=False, **over)
    if cfg.hd * cfg.n_heads != dec["n_heads"] * dec["head_dim"]:
        raise ValueError("head_dim does not match the program's heads")
    return cfg


@partial(jax.jit, static_argnums=1)
def _draw_params(key, cfg):
    """Seeded weights; biases and norm parameters are drawn too, so no
    term of the layer is zero or one."""
    from repro.models import transformer as tfm
    params = tfm.init(jax.random.fold_in(key, 0), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    drawn = []
    for i, (path, a) in enumerate(leaves):
        if str(path[-1].key) in ("scale", "nbias", "bq", "bk", "bv", "bo",
                                 "mb1", "mb2"):
            k = jax.random.fold_in(jax.random.fold_in(key, 1), i)
            a = (a + 0.1 * jax.random.normal(k, a.shape)).astype(a.dtype)
        drawn.append(a)
    return jax.tree_util.tree_unflatten(tree, drawn)


def _new_session(ctx, state, index: int) -> dict:
    """`batch` prompts, seeded slices of the stream, prefilled into the
    decode caches; the first decode token of each is the prefill's greedy
    choice."""
    wl, plen = ctx.workload, ctx.workload["prompt_len"]
    stream = state["stream_host"]
    offsets = ctx.rng.integers(0, len(stream) - plen, size=wl["batch"])
    caches, first = state["caches"], []
    for b, o in enumerate(offsets):
        logits, caches = state["prefill"](
            state["params"], caches, jnp.asarray(stream[None, o:o + plen]),
            jnp.int32(b))
        first.append(jnp.argmax(logits[:, 0], -1))
    state["caches"] = caches
    tok = jnp.concatenate(first).astype(jnp.int32)
    return {"index": index, "offsets": offsets, "pos": plen,
            "tok": tok[:, None], "first": np.asarray(tok), "generated": []}


def setup(ctx):
    from repro.models import transformer as tfm
    from repro.models.sharding import Rules

    cfg, wl = ctx.config, ctx.workload
    dec, knn = cfg["decoder"], cfg["knn"]
    model = model_config(dec)
    rules = Rules(batch=(), fsdp=(), tensor=(), expert=())
    key = jax.random.PRNGKey(ctx.seed)
    params = _draw_params(key, model)

    rows, chunk = cfg["rows"], cfg["prefill_chunk"]
    n_tok = -(-rows // chunk) * chunk + 1
    stream = jax.random.randint(jax.random.fold_in(key, 2), (n_tok,), 0,
                                dec["vocab_size"], jnp.int32)
    mem_cfg = datastore_config(model, rows, search_config(cfg))
    t0 = time.perf_counter()
    keys = datastore_keys(params, model, rules, stream, rows, chunk)
    ctx.log(f"datastore keys: {rows} of d={cfg['dim']} in "
            f"{time.perf_counter() - t0:.3f} s")
    stream_host = np.asarray(stream)
    with ctx.timed("store_write_s"):
        store = build_datastore(mem_cfg, keys, stream_host[1:rows + 1],
                                cfg["write_batch"])
        jax.block_until_ready(store)
    ctx.log(f"datastore: programmed in {ctx.timers['store_write_s']:.3f} s")

    max_seq = dec["max_position_embeddings"]
    state = {
        "model": model, "params": params, "store": store, "keys": keys,
        "stream_host": stream_host, "max_seq": max_seq,
        "caches": jax.jit(lambda: tfm.init_cache(model, wl["batch"],
                                                 max_seq))(),
        "prefill": make_prefill_into_step(model, rules, max_seq),
        "step": make_serve_step_with_mcam(
            model, rules, mem_cfg, lam=knn["lambda"], k=knn["k"],
            mode=wl["mode"]),
        "sessions": [],
    }
    t0 = time.perf_counter()
    session = _new_session(ctx, state, 0)
    state["sessions"].append(session)
    ctx.log(f"session 0: {wl['batch']} prompts of {wl['prompt_len']} "
            f"prefilled in {time.perf_counter() - t0:.3f} s")
    out, state["caches"] = state["step"](
        params, state["caches"], {"tokens": session["tok"]},
        jnp.int32(session["pos"]), store)
    np.asarray(out["token"])                     # the step's program, warmed
    return state


def window(ctx, state):
    params, store, step = state["params"], state["store"], state["step"]
    n_keep = ctx.workload["check_steps"]
    session = state["sessions"][-1]
    caches = state["caches"]
    kept, latencies = [], []
    t_start = time.perf_counter()
    deadline = t_start + ctx.seconds
    i = 0
    while True:
        if session["pos"] >= state["max_seq"]:
            with ctx.span("bench.prefill"):
                state["caches"] = caches
                session = _new_session(ctx, state, len(state["sessions"]))
                state["sessions"].append(session)
                caches = state["caches"]
        t0 = time.perf_counter()
        with ctx.span("bench.decode"):
            out, caches = step(params, caches, {"tokens": session["tok"]},
                               jnp.int32(session["pos"]), store)
        with ctx.span("bench.readback"):
            tokens = np.asarray(out["token"])
        t1 = time.perf_counter()
        latencies.append(t1 - t0)
        session["generated"].append(tokens)
        item = (session["index"], session["pos"], out)
        if len(kept) < n_keep:                   # reservoir over the window
            kept.append(item)
        else:
            j = int(ctx.rng.integers(0, i + 1))
            if j < n_keep:
                kept[j] = item
        session["tok"] = out["token"][:, None]
        session["pos"] += 1
        i += 1
        if t1 >= deadline:
            break
    seconds = t1 - t_start
    state["caches"], state["kept"] = caches, kept
    lat = np.asarray(latencies) * 1e3
    med = float(np.median(lat))
    batch = ctx.workload["batch"]
    ctx.log(f"steps {i} in {len(state['sessions'])} session(s): median "
            f"{med:.3f} ms, p95 {np.percentile(lat, 95):.3f}, max "
            f"{lat.max():.3f} (step {int(lat.argmax())}), first {lat[0]:.3f}"
            f"; time over 1.1x the median "
            f"{float(np.sum(np.maximum(lat - 1.1 * med, 0))):.1f} ms in "
            f"{int(np.sum(lat > 1.1 * med))} steps")
    return {"attempted": i * batch, "failed": 0,
            "metrics": {"qps": i * batch / seconds,
                        "search_p95_ms": float(np.percentile(latencies, 95))
                        * 1e3},
            "counts": {"steps": i, "batches": i, "tokens": i * batch,
                       "sessions": len(state["sessions"]),
                       "seconds": seconds}}


def _sequence(state, session: dict, row: int) -> np.ndarray:
    """The tokens of one sequence of a session: its prompt, the prefill's
    choice, then every token the window chose."""
    plen = session["pos"] - len(session["generated"])
    o = session["offsets"][row]
    gen = [g[row] for g in session["generated"]]
    return np.concatenate([state["stream_host"][o:o + plen],
                           [session["first"][row]], gen]).astype(np.int32)


def program_outputs(ctx, state) -> dict:
    """What the timed path produced, sampled by the seed, on the host: the
    kept steps' outputs, stored rows, and the keys of one stream chunk.
    The store and the caches are freed."""
    cfg = ctx.config
    n, chunk = cfg["rows"], cfg["prefill_chunk"]
    rows = np.sort(ctx.rng.choice(n, min(ctx.workload["check_rows"], n),
                                  replace=False))
    c0 = int(ctx.rng.integers(0, -(-n // chunk))) * chunk
    key_rows = np.arange(c0, min(c0 + chunk, n))
    store = state.pop("store")
    idx = jnp.asarray(rows)
    out = {"rows": rows, "values": np.asarray(store.values[idx]),
           "s_grid": np.asarray(store.s_grid[idx]),
           "labels": np.asarray(store.labels[idx]),
           "key_rows": key_rows, "keys": state["keys"][key_rows],
           "steps": [dict(session=s, pos=p, **jax.tree_util.tree_map(
               np.asarray, o)) for s, p, o in state.pop("kept")]}
    del store
    state.pop("caches", None)
    return out


def _lm_reference(ctx, state, prog, **control) -> dict:
    """{(session, row, pos): (reference log-probs (V,), post-final-norm
    hidden state (d,))} at the sampled steps, for every sequence."""
    dec = ctx.config["decoder"]
    out = {}
    for s in sorted({st["session"] for st in prog["steps"]}):
        session = state["sessions"][s]
        positions = sorted(st["pos"] for st in prog["steps"]
                           if st["session"] == s)
        for row in range(ctx.workload["batch"]):
            lp, h = ref.forward(state["params"], dec,
                                _sequence(state, session, row), positions,
                                **control)
            for p, v, hv in zip(positions, np.asarray(lp), np.asarray(h)):
                out[(s, row, p)] = (v, hv)
    return out


def _key_reference(ctx, state, prog, **control) -> np.ndarray:
    """(P, d) the reference's hidden states over the sampled stream chunk,
    prefilled as a sequence of its own: the keys of `prog["key_rows"]`."""
    rows = prog["key_rows"]
    return np.asarray(ref.hidden_states(
        state["params"], ctx.config["decoder"],
        state["stream_host"][rows[0]:rows[-1] + 1], rows - rows[0],
        **control))


def _knn_reference(ctx, state, prog, dtype=jnp.float32) -> dict:
    """The reference's datastore over the program's own keys (its values at
    the sampled rows), and its search on every sampled step's queries."""
    cfg = ctx.config
    k = cfg["knn"]["k"]
    keys = jnp.asarray(state["keys"])
    wb = cfg["write_batch"]
    lo, hi = ref_avss.calibrate(keys[:wb], cfg["clip_std"], dtype)
    quant = jax.jit(ref_avss.quantize, static_argnums=(1, 4))
    levels = 3 * cfg["cl"] + 1
    values = jnp.concatenate([quant(keys[s:s + wb], levels, lo, hi, dtype)
                              for s in range(0, keys.shape[0], wb)])
    del keys
    labels = jnp.asarray(state["stream_host"][1:cfg["rows"] + 1])
    sample = jnp.asarray(prog["rows"])
    out = {"values": np.asarray(values[sample]),
           "s_grid": np.asarray(ref_avss.layout(
               values[sample], cfg["cl"], cfg["mcam"]["string_len"])),
           "labels": np.asarray(labels[sample]), "steps": []}
    qidx = jnp.arange(ctx.workload["batch"], dtype=jnp.uint32)
    for st in prog["steps"]:
        qw = ref_avss.quantize(jnp.asarray(st["query"]), 4, lo, hi, dtype)
        r = ref_avss.search(qw, values, labels, qidx, cfg["cl"], k,
                            cfg["mcam"], dtype)
        out["steps"].append({f: np.asarray(r[f])
                             for f in ("indices", "dist", "labels", "votes")})
    return out


def compare(ctx, prog, lm_want, keys_want, knn_want, lm_got=None,
            keys_got=None) -> dict:
    """The numbers of the check, against the float32 reference. `lm_got`
    and `keys_got` replace the program's LM log-probs and queries, and its
    keys, by another's (a control)."""
    knn = ctx.config["knn"]
    diff = lambda a, b: int(np.sum(np.asarray(a) != np.asarray(b)))
    steps, want = prog["steps"], knn_want["steps"]
    at = {(st["session"], st["pos"]): st for st in steps}
    got = [lm_got[key] if lm_got is not None else
           (at[key[0], key[2]]["lm_logprobs"][key[1]],
            at[key[0], key[2]]["query"][key[1]]) for key in lm_want]
    kls = ref.kl(np.stack([v for v, _ in lm_want.values()]),
                 np.stack([g for g, _ in got]))
    q_err = ref.rel_err(np.stack([h for _, h in lm_want.values()]),
                        np.stack([q for _, q in got]))
    k_err = ref.rel_err(keys_want,
                        prog["keys"] if keys_got is None else keys_got)
    mix = [float(np.max(np.abs(st["logprobs"] - np.asarray(ref.knn_mixture(
        st["lm_logprobs"], st["votes"], st["labels"], knn["lambda"],
        knn["temperature"]))))) for st in steps]
    votes = sum(diff(st["votes"], w["votes"]) for st, w in zip(steps, want))
    n_votes = max(1, sum(np.size(w["votes"]) for w in want))
    return {
        "lm_logprob_err": float(np.max(kls)),
        "query_err": float(np.max(q_err)),
        "key_err": float(np.max(k_err)),
        "store_mismatch": diff(prog["values"], knn_want["values"])
        + diff(prog["s_grid"], knn_want["s_grid"])
        + diff(prog["labels"], knn_want["labels"]),
        "shortlist_mismatch": sum(diff(st[f], w[f]) for st, w in
                                  zip(steps, want)
                                  for f in ("indices", "dist", "labels")),
        "vote_mismatch_pct": 100.0 * votes / n_votes,
        "mix_err": max(mix, default=0.0),
        "token_mismatch": sum(diff(st["token"], st["logprobs"].argmax(-1))
                              for st in steps),
    }


# set from the readings in PERF.md, section 2
LIMITS = {"lm_logprob_err": 1e-3, "query_err": 0.05, "key_err": 0.05,
          "store_mismatch": 0, "shortlist_mismatch": 0,
          "vote_mismatch_pct": 1.0, "mix_err": 1e-3, "token_mismatch": 0}


def check(ctx, state):
    prog = program_outputs(ctx, state)
    found = compare(ctx, prog, _lm_reference(ctx, state, prog),
                    _key_reference(ctx, state, prog),
                    _knn_reference(ctx, state, prog))
    return {k: (found[k], LIMITS[k]) for k in CHECKED}


def control(ctx, state):
    """Against the float32 reference, on the same samples: the program's
    readings, and those of the controls put in its place -- the reference
    with its weights rounded to float8_e4m3 (the precision below the
    stated bfloat16), the reference attending past the window, and the
    kNN half (datastore, search, mixture) in bfloat16."""
    knn = ctx.config["knn"]
    prog = program_outputs(ctx, state)
    want = (_lm_reference(ctx, state, prog), _key_reference(ctx, state, prog),
            _knn_reference(ctx, state, prog))
    out = {"program": compare(ctx, prog, *want)}
    for name, kw in (("float8_weights", {"weight_dtype": jnp.float8_e4m3fn}),
                     ("no_window", {"window": False})):
        out[name] = compare(ctx, prog, *want,
                            lm_got=_lm_reference(ctx, state, prog, **kw),
                            keys_got=_key_reference(ctx, state, prog, **kw))
    bf = _knn_reference(ctx, state, prog, dtype=jnp.bfloat16)
    steps = []
    for st, c in zip(prog["steps"], bf["steps"]):
        mixed = np.asarray(ref.knn_mixture(
            st["lm_logprobs"], c["votes"], c["labels"], knn["lambda"],
            knn["temperature"], jnp.bfloat16)).astype(np.float32)
        steps.append(dict(st, **c, logprobs=mixed, token=mixed.argmax(-1)))
    out["bfloat16_knn"] = compare(
        ctx, dict(prog, values=bf["values"], s_grid=bf["s_grid"],
                  steps=steps), *want)
    return out
