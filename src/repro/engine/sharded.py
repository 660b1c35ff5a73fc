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
engine: when a shard's local rows reach `fused_min_rows` (or the backend is
'fused'), phase 1 runs the fused Pallas shortlist kernel
(kernels/shortlist.py, HBM O(B*k_loc + N_loc*4d)) inside the shard_map
body -- masked rows (ragged pads, empty slots) are penalised NATIVELY in
the kernel with the integer-exact SHORTLIST_MASK_PENALTY, and ragged
(non-tile-aligned) local blocks are padded inside the kernel wrapper.
Below the threshold (and on the 'ref' backend) the readable dense local
matmul + lax.top_k remains, bit-identically.

Exactness argument for the two-phase path (verified by
tests/test_engine.py::test_sharded_two_phase_bit_identical):

* Shortlist distances are integer-valued f32 (AVSS LUT entries are small
  integers, one-hot queries are 0/1, f32 accumulation is exact below 2**24),
  so every shard computes the same exact distance a single device would --
  fused or dense.
* `jax.lax.top_k` ranks by (value, index), and the fused kernel reproduces
  that order exactly (ties included): a support in the GLOBAL top-k is
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

from typing import TYPE_CHECKING, Any, Sequence

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


def _use_fused(backend: str, rows_loc: int,
               fused_min_rows: int | None) -> bool:
    """Shared shard-local dispatch rule: the fused Pallas shortlist kernel
    engages on any kernel backend once a shard's local rows reach the
    threshold, and always on the 'fused' backend; the 'ref' backend (and
    fused_min_rows=None, the raw-array default) keeps the dense local
    matmul as the readable reference."""
    if backend == "fused":
        return True
    return (backend != "ref" and fused_min_rows is not None
            and rows_loc >= fused_min_rows)


def _local_shortlist(q1h: jax.Array, proj_loc: jax.Array,
                     valid_loc: jax.Array, k_loc: int, *, fused: bool,
                     packed: jax.Array | None = None,
                     pack_bits: int | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Block shortlist shared by every dispatch site (per shard inside the
    shard_map bodies here, and the unsharded dense `ideal` route in
    engine.py): top-k_loc of the rows by exact integer LUT distance
    (+ native mask penalty), fused or dense -- bit-identical either way
    (the kernel reproduces lax.top_k's (distance, row) order). When the
    store provides its bit-packed projection (`packed`/`pack_bits`), the
    fused kernel streams that 4-8x smaller operand instead of proj_loc."""
    with jax.named_scope(hc.SHORTLIST_SCOPE):
        if fused:
            from repro.kernels import shortlist as shortlist_kernel
            if packed is not None:
                return shortlist_kernel.lut_shortlist_pallas(
                    q1h, None, k_loc, valid=valid_loc, packed=packed,
                    pack_bits=pack_bits)
            return shortlist_kernel.lut_shortlist_pallas(
                q1h, proj_loc, k_loc, valid=valid_loc)
        from repro.kernels import ops as kernel_ops
        dist = q1h @ proj_loc.astype(jnp.float32).T        # (B, N_loc)
        dist = dist + jnp.where(valid_loc, 0.0,
                                kernel_ops.SHORTLIST_MASK_PENALTY)[None]
        neg, idx = jax.lax.top_k(-dist, k_loc)
        return -neg, idx


def sharded_two_phase_search(q_values: jax.Array, s_values: jax.Array,
                             cfg: SearchConfig, mesh: Mesh,
                             axes: Sequence[str] = ("data",),
                             k: int = 64, valid: jax.Array | None = None,
                             labels: jax.Array | None = None,
                             s_grid: jax.Array | None = None,
                             proj: jax.Array | None = None,
                             packed: jax.Array | None = None,
                             pack_bits: int | None = None,
                             backend: str = "ref",
                             fused_min_rows: int | None = None
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
    packed: optional bit-packed projection (row-sharded,
    MemoryStore.proj_packed); the fused per-shard shortlist then streams
    this 4-8x smaller operand instead of proj, bit-identically.
    backend / fused_min_rows: per-shard shortlist dispatch (see
    `_use_fused`); the default (ref, None) keeps the dense local matmul.
    Returns {votes (B, k), dist (B, k), indices (B, k) global rows
    [, labels (B, k)], iterations} -- bit-identical to
    RetrievalEngine.two_phase(q, s, k, valid) on a single device,
    whichever shortlist path engages.
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
    fused = _use_fused(backend, N // n_shards, fused_min_rows)

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
    if packed is not None:
        extras.append(packed)
        extra_specs.append(P(axes))
        if pack_bits is None:
            # fallback for callers that packed from `proj` right here; the
            # engine passes store.pack_bits (the authoritative pack-time
            # width) so a bf16-vs-f32 proj dtype can never mis-unpack
            pack_bits = kernel_ops.projection_pack_bits(
                enc, proj.dtype if proj is not None else jnp.bfloat16)
    else:
        pack_bits = None
    ax = axes[0] if len(axes) == 1 else tuple(axes)

    def local(q1h_: jax.Array, q_grid_: jax.Array, s_loc: jax.Array,
              valid_loc: jax.Array,
              *rest: jax.Array) -> tuple[jax.Array, ...]:
        rest_l = list(rest)
        labels_loc = rest_l.pop(0) if labels is not None else None
        s_grid_loc = rest_l.pop(0) if s_grid is not None else None
        proj_loc = rest_l.pop(0) if proj is not None else None
        packed_loc = rest_l.pop(0) if packed is not None else None
        # phase 1 on local rows: exact integer-valued distances, fused
        # kernel or dense MXU matmul (same LUT projection as
        # kernels/ops.support_projection, materialised at write time when
        # the store provides `proj` / its bit-packed `packed` twin)
        with jax.named_scope(hc.SHORTLIST_SCOPE):
            offset = _shard_index(mesh, axes) * jnp.int32(s_loc.shape[0])
            if proj_loc is None:                       # (N_loc, 4d)
                proj_loc = lut.T[s_loc].reshape(s_loc.shape[0], -1)
            d_loc, idx_loc = _local_shortlist(
                q1h_, proj_loc, valid_loc, k_loc, fused=fused,
                packed=packed_loc, pack_bits=pack_bits)
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
                         k: int = 16, backend: str = "ref",
                         fused_min_rows: int | None = None,
                         packed: jax.Array | None = None,
                         pack_bits: int | None = None,
                         enc: Any = None) -> dict[str, jax.Array]:
    """Ideal-digital-distance block search (no rescore; cheap serving path).

    q_onehot: (B, 4d) replicated query one-hots; proj: (N, 4d) row-sharded
    LUT projections; labels: (N,) row-sharded (< 0 marks empty slots --
    their distance carries the integer-exact SHORTLIST_MASK_PENALTY, the
    same masking the two-phase and unsharded ideal paths use, so results
    stay bit-identical to the single-device fused/dense ideal search even
    when masked rows reach the top-k).
    backend / fused_min_rows: per-shard shortlist dispatch (see
    `_use_fused`); above the threshold each shard streams through the fused
    Pallas shortlist kernel instead of the dense (B, N_loc) local matmul.
    packed / enc: optional bit-packed projection (row-sharded,
    MemoryStore.proj_packed) and its encoding; the fused path then streams
    the 4-8x smaller operand, bit-identically.
    Collective volume is O(B * k * shards), independent of capacity.
    Returns {dist, votes=-dist, labels, indices} each (B, k').
    """

    rows_loc = proj.shape[0] // int(np.prod([mesh.shape[a] for a in axes]))
    fused = _use_fused(backend, rows_loc, fused_min_rows)
    extras: list[jax.Array] = []
    extra_specs: list[P] = []
    if packed is not None and (pack_bits is not None or enc is not None):
        extras.append(packed)
        extra_specs.append(P(axes))
        if pack_bits is None:            # fallback: derive from enc + proj
            from repro.kernels import ops as kernel_ops
            pack_bits = kernel_ops.projection_pack_bits(enc, proj.dtype)
    else:
        pack_bits = None

    def local(qr: jax.Array, proj_loc: jax.Array, labels_loc: jax.Array,
              *rest: jax.Array
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
        packed_loc = rest[0] if rest else None
        kk = min(k, proj_loc.shape[0])
        d_loc, idx = _local_shortlist(qr, proj_loc, labels_loc >= 0, kk,
                                      fused=fused, packed=packed_loc,
                                      pack_bits=pack_bits)
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
            in_specs=(P(), P(axes), P(axes), *extra_specs),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )(q_onehot, proj, labels, *extras)
    return {"dist": dist, "labels": labels_out, "votes": -dist,
            "indices": indices}
