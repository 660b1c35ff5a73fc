"""Sharded retrieval: block-parallel search over a row-sharded store.

Two flavours:

  sharded_two_phase_search   per-shard shortlist + exact noisy rescore,
                             then an all-gather of the per-shard top-k
                             (dist, index, label) TRIPLET only + global
                             top-k merge; the merged candidates' votes are
                             recovered with one (B, k) psum (each global
                             row is owned by exactly one shard, so the
                             ownership-masked partial sums are exact --
                             no vote tensor ever rides the all-gather).
                             Votes are BIT-IDENTICAL to the single-device
                             two-phase. Ragged stores arrive pre-padded by
                             MemoryStore.shard (label -1 pad rows, masked
                             by the phase-1 penalty).
  sharded_ideal_search       ideal-digital-distance only (the cheap serving
                             path formerly inlined in core/memory.py).

Both paths share ONE per-shard shortlist implementation with the unsharded
engine (`_local_shortlist`): phase 1 is one MXU dot of the query one-hots
against the shard's LUT projection, the integer-exact
SHORTLIST_MASK_PENALTY on masked rows (ragged pads, empty slots) and XLA's
exact lax.top_k. Where the (B, N_loc) f32 distance block would exceed
SHORTLIST_DENSE_MAX_BYTES, the same block runs over query chunks that fit
it (`shortlist_chunk`), bit-identically.

Exactness argument for the two-phase path (verified by
tests/test_engine.py::test_sharded_two_phase_bit_identical):

* Shortlist distances are integer-valued f32 (AVSS LUT entries are small
  integers, one-hot queries are 0/1, f32 accumulation is exact below 2**24),
  so every shard computes the same exact distance a single device would.
* `jax.lax.top_k` ranks by (value, index), ties included: a support in
  the GLOBAL top-k is
  necessarily in its shard's LOCAL top-k under the same order, so no global
  candidate is lost by local pruning.
* The all-gather stacks shards in mesh-axis-major order -- the same order a
  row-sharded array is laid out in -- so a STABLE argsort over the gathered
  distances resolves ties by ascending global support index, exactly like
  single-device top_k.
* The rescore feeds GLOBAL support indices to the noise counters, so the
  noisy vote of support n for query b is the same number on every shard.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analysis import hlo_contracts as hc

if TYPE_CHECKING:
    from jax.sharding import Mesh

    from repro.core.avss import SearchConfig


def _shard_index(mesh: Mesh, axes: Sequence[str]) -> jax.Array:
    """Row-major linear index of this shard over `axes` (inside shard_map)."""
    shard = jnp.int32(0)
    for a in axes:
        shard = shard * jnp.int32(mesh.shape[a]) \
            + jax.lax.axis_index(a).astype(jnp.int32)
    return shard


def _gather_candidates(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """(B, kk) per-shard -> (B, S * kk) shard-major (ascending global rows)."""
    ax = axes[0] if len(axes) == 1 else tuple(axes)
    stacked = jax.lax.all_gather(x, ax, tiled=False).reshape(-1, *x.shape)
    return jnp.moveaxis(stacked, 0, 1).reshape(x.shape[0], -1)


# Largest (B, N_loc) f32 distance block phase 1 materialises before
# lax.top_k. On a TPU v5e XLA's dense dot + exact top_k ranks B=64 queries
# over 1,572,864 rows in 11.5 ms (a 403 MB block); 1 GiB is under 7% of a
# v5e's 15.75 GiB of HBM, room the store and the phase-2 operands leave
# over. A larger block (a big multi-tenant flush, a huge shard) is ranked
# in query chunks that each fit it (`shortlist_chunk`).
SHORTLIST_DENSE_MAX_BYTES = 1 << 30


def shortlist_chunk(batch: int, rows: int) -> int:
    """Queries per phase-1 block over `rows` rows: all `batch` of them while
    their (batch, rows) f32 distance block fits SHORTLIST_DENSE_MAX_BYTES,
    else the most whose block does. A shape rule of the call, read at
    trace time; one query's block over the budget raises.

    >>> shortlist_chunk(64, 1_572_864)          # the omniglot cell: one block
    64
    >>> shortlist_chunk(64, 1 << 24)            # 64 MiB a query: 16 a block
    16
    """
    per_query = rows * 4
    if per_query > SHORTLIST_DENSE_MAX_BYTES:
        raise ValueError(
            f"phase 1 over {rows} rows needs a {per_query}-byte f32 "
            f"distance row per query, more than SHORTLIST_DENSE_MAX_BYTES "
            f"({SHORTLIST_DENSE_MAX_BYTES}); shard the store so each shard "
            f"holds fewer rows")
    return min(batch, SHORTLIST_DENSE_MAX_BYTES // per_query)


def map_query_chunks(fn: Callable[[jax.Array], Any], chunk: int,
                     q: jax.Array) -> Any:
    """`fn(q)` over the leading (query) axis of `q`, `chunk` queries at a
    time: one call when `chunk` covers the batch, else a `jax.lax.map` over
    the batch padded to a multiple of `chunk`, so one chunk's block is live
    at a time. Each query's result depends on that query alone, so the
    chunked results equal the unchunked ones bit for bit."""
    b = q.shape[0]
    if chunk >= b:
        return fn(q)
    n = -(-b // chunk)
    q = jnp.pad(q, [(0, n * chunk - b)] + [(0, 0)] * (q.ndim - 1))
    out = jax.lax.map(fn, q.reshape((n, chunk) + q.shape[1:]))
    return jax.tree_util.tree_map(
        lambda y: y.reshape((n * chunk,) + y.shape[2:])[:b], out)


def _local_shortlist(q1h: jax.Array, proj_loc: jax.Array,
                     valid_loc: jax.Array | None, k_loc: int
                     ) -> tuple[jax.Array, jax.Array]:
    """Block shortlist shared by every search site (per shard inside the
    shard_map bodies here, the unsharded engine, the routed blocks):
    top-k_loc of the rows by exact integer LUT distance (+ the mask
    penalty on rows `valid_loc` masks), in (distance, row) order, in query
    chunks where the (B, N_loc) block is over the budget
    (`shortlist_chunk`).

    It dots against proj_loc as stored, accumulating in f32: the one-hot
    is 0/1 and every stored entry is a value of proj_loc's own dtype, so
    each product is exact and so is any sum below 2**24. A bf16 projection
    therefore needs no per-call f32 copy; an f32 one asks for full
    precision, which the TPU's default one-pass bf16 would round."""
    from repro.kernels import ops as kernel_ops
    exact = (None if proj_loc.dtype == jnp.bfloat16
             else jax.lax.Precision.HIGHEST)

    def block(q: jax.Array) -> tuple[jax.Array, jax.Array]:
        dist = jax.lax.dot_general(                        # (b, N_loc)
            q.astype(proj_loc.dtype), proj_loc, (((1,), (1,)), ((), ())),
            precision=exact, preferred_element_type=jnp.float32)
        if valid_loc is not None:
            dist = dist + jnp.where(valid_loc, 0.0,
                                    kernel_ops.SHORTLIST_MASK_PENALTY)[None]
        neg, idx = jax.lax.top_k(-dist, k_loc)
        return -neg, idx

    with jax.named_scope(hc.SHORTLIST_SCOPE):
        return map_query_chunks(
            block, shortlist_chunk(q1h.shape[0], proj_loc.shape[0]), q1h)


def sharded_two_phase_search(q_values: jax.Array, s_values: jax.Array,
                             cfg: SearchConfig, mesh: Mesh,
                             axes: Sequence[str] = ("data",),
                             k: int = 64, valid: jax.Array | None = None,
                             labels: jax.Array | None = None,
                             s_grid: jax.Array | None = None,
                             proj: jax.Array | None = None
                             ) -> dict[str, jax.Array]:
    """Two-phase AVSS over a store row-sharded on `axes`.

    q_values: (B, d) ints in [0, 4), replicated.
    s_values: (N, d) ints, row-sharded (N divisible by the shard count;
    `MemoryStore.shard` pads ragged splits with label -1 rows first).
    valid: optional (N,) bool, row-sharded like s_values; masked rows get
    the integer-exact SHORTLIST_MASK_PENALTY on their phase-1 distance.
    labels: optional (N,) int32, row-sharded. When given, each shard looks
    up its local candidates' labels and contributes them to the all-gather
    (the merge then never touches the globally-sharded label column), and
    the result gains a "labels" key.
    s_grid: optional (N, seg, L, sl) write-time string grid (row-sharded,
    MemoryStore.s_grid); omitted -> each shard lays out its rows here.
    proj: optional (N, 4d) write-time LUT projection (row-sharded,
    MemoryStore.proj); omitted -> each shard projects its rows here.
    Returns {votes (B, k), dist (B, k), indices (B, k) global rows
    [, labels (B, k)], iterations} -- bit-identical to
    RetrievalEngine.two_phase(q, s, k, valid) on a single device.
    """
    from repro.core import avss as avss_lib
    from repro.kernels import ops as kernel_ops

    assert cfg.mode == "avss", "two-phase search shortlists with the AVSS LUT"
    enc = cfg.enc
    sl = cfg.mcam.string_len
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    N = s_values.shape[0]
    assert N % n_shards == 0, (
        f"store rows ({N}) must divide evenly over {n_shards} shards "
        f"(MemoryStore.shard pads ragged splits)")
    k = min(k, N)
    k_loc = min(k, N // n_shards)

    with jax.named_scope(hc.SHORTLIST_SCOPE):
        q1h = kernel_ops.query_onehot(q_values, jnp.float32)   # (B, 4d)
    with jax.named_scope(hc.RESCORE_SCOPE):
        q_grid = avss_lib.layout_query(q_values, enc, "avss", sl)
        weights = enc.weights_array()
        thresholds = jnp.asarray(cfg.mcam.thresholds())
    # LUT built eagerly OUTSIDE the shard_map trace (it is a compile-time
    # constant of the encoding) and closed over by the local function.
    from repro.core.encodings import avss_sum_lut
    lut = jnp.asarray(avss_sum_lut(enc), jnp.float32)          # (4, levels)
    if valid is None:
        # keep the shard_map arity fixed; +0.0 is exact, parity unaffected
        with jax.named_scope(hc.SHORTLIST_SCOPE):
            valid = jnp.ones((N,), bool)
    # optional row-sharded extras keep the arity dynamic but the specs tied
    extras: list[jax.Array] = []
    extra_specs: list[P] = []
    if labels is not None:
        extras.append(labels)
        extra_specs.append(P(axes))
    if s_grid is not None:
        extras.append(s_grid)
        extra_specs.append(P(axes))
    if proj is not None:
        extras.append(proj)
        extra_specs.append(P(axes))
    ax = axes[0] if len(axes) == 1 else tuple(axes)

    def local(q1h_: jax.Array, q_grid_: jax.Array, s_loc: jax.Array,
              valid_loc: jax.Array,
              *rest: jax.Array) -> tuple[jax.Array, ...]:
        rest_l = list(rest)
        labels_loc = rest_l.pop(0) if labels is not None else None
        s_grid_loc = rest_l.pop(0) if s_grid is not None else None
        proj_loc = rest_l.pop(0) if proj is not None else None
        # phase 1 on local rows: exact integer-valued distances by one MXU
        # dot (same LUT projection as kernels/ops.support_projection,
        # materialised at write time when the store provides `proj`)
        with jax.named_scope(hc.SHORTLIST_SCOPE):
            offset = _shard_index(mesh, axes) * jnp.int32(s_loc.shape[0])
            if proj_loc is None:                       # (N_loc, 4d)
                proj_loc = lut.T[s_loc].reshape(s_loc.shape[0], -1)
            d_loc, idx_loc = _local_shortlist(q1h_, proj_loc, valid_loc,
                                              k_loc)
            gidx = idx_loc + offset
        # phase 2 on local candidates, GLOBAL indices for the noise counters
        with jax.named_scope(hc.RESCORE_SCOPE):
            if s_grid_loc is None:                     # read-time layout
                s_grid_loc = avss_lib.layout_support(s_loc, enc, sl)
            votes = kernel_ops.rescore_shortlist(
                q_grid_, s_grid_loc, idx_loc, weights, cfg, thresholds,
                noise_idx=gidx)
        # merge: all-gather ONLY the per-shard (dist, global index[, label])
        # triplet -- a stable sort by distance == (distance, global row)
        # order selects the global top-k. Votes never ride the gather: each
        # selected global row is owned by exactly one shard (shard offsets
        # partition the index space, local candidates are distinct), so the
        # ownership-masked partial sum holds that shard's rescored vote and
        # zeros elsewhere, and one (B, k) psum recovers the merged votes
        # exactly (adding f32 zeros is exact -- bit-parity preserved).
        with jax.named_scope(hc.MERGE_SCOPE):
            d_all = _gather_candidates(d_loc, axes)
            i_all = _gather_candidates(gidx, axes)
            order = jnp.argsort(d_all, axis=-1, stable=True)[:, :k]
            take = lambda x: jnp.take_along_axis(x, order, axis=1)
            d_k, i_k = take(d_all), take(i_all)
            own = i_k[:, :, None] == gidx[:, None, :]     # (B, k, k_loc)
            v_part = jnp.sum(jnp.where(own, votes[:, None, :], 0.0), axis=2)
            v_k = jax.lax.psum(v_part, ax)
            outs = (v_k, d_k, i_k)
            if labels_loc is not None:
                l_all = _gather_candidates(labels_loc[idx_loc], axes)
                outs = outs + (take(l_all),)
        return outs

    # ops XLA hoists out of the per-shard body carry the shard_map call's
    # own metadata: they convert phase 1's operands
    with jax.named_scope(hc.SHORTLIST_SCOPE):
        out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), P(axes), P(axes), *extra_specs),
            out_specs=(P(),) * (3 + (labels is not None)),
            check_vma=False,
        )(q1h, q_grid, s_values, valid, *extras)
    res = {"votes": out[0], "dist": out[1], "indices": out[2],
           "iterations": avss_lib.search_iterations(
               q_values.shape[-1], enc, "avss", sl)}
    if labels is not None:
        res["labels"] = out[3]
    return res


def sharded_ideal_search(q_onehot: jax.Array, proj: jax.Array,
                         labels: jax.Array, mesh: Mesh,
                         axes: Sequence[str] = ("data",),
                         k: int = 16) -> dict[str, jax.Array]:
    """Ideal-digital-distance block search (no rescore; cheap serving path).

    q_onehot: (B, 4d) replicated query one-hots; proj: (N, 4d) row-sharded
    LUT projections; labels: (N,) row-sharded (< 0 marks empty slots --
    their distance carries the integer-exact SHORTLIST_MASK_PENALTY, the
    same masking the two-phase and unsharded ideal paths use, so results
    stay bit-identical to the single-device ideal search even when masked
    rows reach the top-k).
    Collective volume is O(B * k * shards), independent of capacity.
    Returns {dist, votes=-dist, labels, indices} each (B, k').
    """

    def local(qr: jax.Array, proj_loc: jax.Array, labels_loc: jax.Array
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
        kk = min(k, proj_loc.shape[0])
        d_loc, idx = _local_shortlist(qr, proj_loc, labels_loc >= 0, kk)
        with jax.named_scope(hc.MERGE_SCOPE):
            offset = _shard_index(mesh, axes) * jnp.int32(proj_loc.shape[0])
            d_all = _gather_candidates(d_loc, axes)
            l_all = _gather_candidates(labels_loc[idx], axes)
            i_all = _gather_candidates(idx + offset, axes)
            order = jnp.argsort(d_all, axis=-1, stable=True)[:, :k]
            take = lambda x: jnp.take_along_axis(x, order, axis=1)
            return take(d_all), take(l_all), take(i_all)

    with jax.named_scope(hc.SHORTLIST_SCOPE):     # as in the two-phase
        dist, labels_out, indices = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(axes), P(axes)),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )(q_onehot, proj, labels)
    return {"dist": dist, "labels": labels_out, "votes": -dist,
            "indices": indices}
