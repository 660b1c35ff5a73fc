"""Seeded inputs of the retrieval cells, made on the device.

Row r of a store belongs to class r // shots and is a noisy copy of its
class centroid; a query is a noisy copy of a random class centroid. The
same seed gives the same rows and queries. Both the program under test and
the reference read these generators, so neither depends on the other.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROW_NOISE = 0.25        # spread of a row (and a query) around its centroid


def class_centroids(key: jax.Array, n_rows: int, dim: int,
                    shots: int) -> jax.Array:
    """(ceil(n_rows / shots), dim) class centroids."""
    n_classes = -(-n_rows // shots)
    return jax.random.normal(jax.random.fold_in(key, 0), (n_classes, dim))


def rows(key: jax.Array, centroids: jax.Array, start: jax.Array, n: int,
         shots: int) -> tuple[jax.Array, jax.Array]:
    """Rows [start, start + n) and their labels (class = row // shots).
    `start` may be traced: one compiled program serves every batch."""
    idx = start + jnp.arange(n, dtype=jnp.int32)
    labels = idx // shots
    noise = jax.random.normal(jax.random.fold_in(key, 1 + start),
                              (n, centroids.shape[1]))
    return centroids[labels] + ROW_NOISE * noise, labels


def query_pool(key: jax.Array, centroids: jax.Array, n_batches: int,
               batch: int) -> tuple[jax.Array, jax.Array]:
    """(n_batches, batch, dim) queries near random centroids, and their
    classes (n_batches, batch)."""
    kc, kn = jax.random.split(jax.random.fold_in(key, 2))
    cls = jax.random.randint(kc, (n_batches, batch), 0, centroids.shape[0])
    noise = jax.random.normal(kn, (n_batches, batch, centroids.shape[1]))
    return centroids[cls] + ROW_NOISE * noise, cls
