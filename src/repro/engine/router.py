"""Phase-0 coarse router: per-shard summary sketches over class centroids.

The hierarchy's top level (ROADMAP item 2, IVF-flavored).  Every
partitioned :class:`~repro.engine.store.MemoryStore` carries a
write-time sketch -- per shard, ``ROUTER_BUCKETS`` class-bucket
centroids in the store's already-calibrated integer domain -- and a
routed search (``SearchRequest.nprobe=p``) scores the sketch with ONE
small dense matmul before dispatching phase 1/2 to the top-p shards
only.

Design constraints, inherited from the serving contract:

* **Integer-exact.** Sketch sums/counts are int32; centroids are exact
  round-half-up integer levels, so the scatter write path and the
  shard-local write-through maintain bit-identical sketches, and
  ``save/restore`` reproduces them deterministically.
* **Scatter-free.** ``bucket_sums`` accumulates through a one-hot int32
  matmul (``jax.ops.segment_sum`` lowers to scatter, which the
  multi-shard write-through contract forbids -- see
  analysis/registry.py `MemoryStore.write` cells).
* **Same mask spelling.** Empty buckets carry ``SHORTLIST_MASK_PENALTY``
  exactly like masked support rows in the shortlist, so they can never
  out-rank a shard with real rows.
* The sketch matmul runs under ``jax.named_scope("router_sketch")`` --
  the contract registry asserts the tag appears iff routing is engaged
  (``nprobe < n_shards``).

>>> import jax.numpy as jnp
>>> vals = jnp.array([[0, 9], [2, 9], [8, 1], [8, 3]])
>>> labs = jnp.array([0, 0, 1, 1])
>>> sums, counts = build_sketch(vals, labs, n_shards=2, n_buckets=2)
>>> sums.shape, counts.shape          # (shards, buckets, dim), (S, R)
((2, 2, 2), (2, 2))
>>> [int(x) for x in sums[0, 0]]      # shard 0, bucket 0: rows 0+1 summed
[2, 18]
>>> cent = sketch_centroids(sums, counts, levels=10)
>>> [int(x) for x in cent[0, 0]]      # exact round-half-up mean levels
[1, 9]
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.encodings import Encoding
from repro.kernels import ops as kernel_ops

#: class buckets per shard sketch (label % ROUTER_BUCKETS).  Small on
#: purpose: the whole sketch is S * R * d int32, and routing cost is one
#: (B, 4d) x (4d, S*R) matmul -- negligible next to one shard's phase 1.
ROUTER_BUCKETS = 8


def bucket_sums(values: jax.Array, labels: jax.Array,
                n_buckets: int = ROUTER_BUCKETS
                ) -> tuple[jax.Array, jax.Array]:
    """Per-bucket int32 (sums (R, d), counts (R,)) of valid rows.

    Rows bucket by ``label % n_buckets``; label -1 (pad/mask sentinel)
    rows contribute nothing.  Accumulation is a one-hot int32 matmul:
    exact, and scatter-free so it is legal inside the multi-shard
    write-through (whose compiled HLO must contain no scatter under any
    spelling).
    """
    lab = labels.astype(jnp.int32)
    valid = lab >= 0
    bucket = jnp.where(valid, lab % n_buckets, 0)
    onehot = ((bucket[:, None] == jnp.arange(n_buckets, dtype=jnp.int32))
              & valid[:, None]).astype(jnp.int32)          # (N, R)
    sums = onehot.T @ values.astype(jnp.int32)             # (R, d)
    counts = jnp.sum(onehot, axis=0)                       # (R,)
    return sums, counts


def build_sketch(values: jax.Array, labels: jax.Array, n_shards: int,
                 n_buckets: int = ROUTER_BUCKETS
                 ) -> tuple[jax.Array, jax.Array]:
    """Full-store sketch: (S, R, d) int32 sums and (S, R) int32 counts.

    Rows partition into ``n_shards`` contiguous blocks (the same row
    blocks ``MemoryStore.shard`` lays out), each sketched independently.
    Deterministic function of (values, labels), so recomputing after
    ``restore`` reproduces the saved store's sketch bit-identically.
    """
    n = values.shape[0]
    if n % n_shards:
        raise ValueError(f"{n} rows do not split into {n_shards} shards")
    rows = n // n_shards
    vals = values.reshape(n_shards, rows, values.shape[1])
    labs = labels.reshape(n_shards, rows)
    return jax.vmap(lambda v, l: bucket_sums(v, l, n_buckets))(vals, labs)


def sketch_centroids(sums: jax.Array, counts: jax.Array,
                     levels: int) -> jax.Array:
    """Integer bucket centroids: exact round-half-up mean, clamped to the
    store's calibrated level grid [0, levels).  Empty buckets yield level
    0 -- harmless, because :func:`route_scores` masks them out."""
    c = jnp.maximum(counts, 1).astype(jnp.int32)[..., None]
    cent = (2 * sums + c) // (2 * c)                   # round-half-up
    return jnp.clip(cent, 0, levels - 1).astype(jnp.int32)


def route_scores(q_values: jax.Array, sketch_sums: jax.Array,
                 sketch_counts: jax.Array, enc: Encoding) -> jax.Array:
    """(B, S) router scores: per shard, the min exact LUT distance from
    each query to the shard's valid bucket centroids.

    The centroids live in the store's calibrated integer domain, so they
    project through the SAME write-time LUT (`support_projection`) as
    real support rows and score with the same one-hot matmul as the
    dense phase-1 -- one (B, 4d) x (4d, S*R) dot.  Empty buckets carry
    ``SHORTLIST_MASK_PENALTY`` (the shortlist's own mask spelling), so a
    shard of pure padding can never beat a shard with real rows.
    """
    with jax.named_scope("router_sketch"):
        s, r, d = sketch_sums.shape
        cent = sketch_centroids(sketch_sums, sketch_counts, enc.levels)
        proj = kernel_ops.support_projection(cent.reshape(s * r, d), enc)
        q1h = kernel_ops.query_onehot(q_values, jnp.float32)
        dist = q1h @ proj.astype(jnp.float32).T            # (B, S*R)
        mask = jnp.where(sketch_counts > 0, 0.0,
                         kernel_ops.SHORTLIST_MASK_PENALTY).reshape(s * r)
        return jnp.min(dist.reshape(-1, s, r) + mask.reshape(s, r)[None],
                       axis=-1)


def top_shards(scores: jax.Array, nprobe: int) -> jax.Array:
    """Top-``nprobe`` shard ids per query, ASCENDING shard id.

    Selection follows the engine's lex rule -- smallest score first,
    ties to the lowest shard id (`lax.top_k` positional tie-break on the
    negated scores).  The ascending sort afterwards is what makes the
    routed search's concatenated candidate blocks globally
    index-ordered, so its (distance, index) merge is bit-identical to
    brute force restricted to the visited shards.
    """
    _, idx = jax.lax.top_k(-scores, nprobe)
    return jnp.sort(idx, axis=-1).astype(jnp.int32)
