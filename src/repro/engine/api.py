"""The typed request/result contract of the unified retrieval API.

Every search in the framework goes through one entry point,

    RetrievalEngine.search(store, queries, SearchRequest) -> SearchResult

replacing the five overlapping ad-hoc paths of the pre-redesign API
(`engine.full` / `engine.two_phase` / `engine.sharded_two_phase`,
`memory.search` / `memory.distributed_search`) and their untyped result
dicts. The request names WHAT to search (mode, k, backend, shard axes,
noise, routing); the store (repro/engine/store.py) carries the
programmed memory and its sharding; the result is a registered pytree safe
to return from jit.

Old -> new mapping (the old entry points remain as thin shims; the full
table with the deprecation policy lives in docs/migration.md):

  engine.full(q, s)                      search(store, q, mode="full")
  engine.two_phase(q, s, k)              search(store, q, mode="two_phase", k)
  engine.sharded_two_phase(q, s, mesh)   search(store.shard(mesh, axes), q,
                                                mode="two_phase", k)
  memory.search(state, q, cfg, ...)      search(store, q, ...)
  memory.distributed_search(state, ...)  search(store.shard(mesh, axes), ...)
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.analysis import hlo_contracts as hc

MODES = ("full", "two_phase", "ideal")


@dataclasses.dataclass(frozen=True)
class SearchRequest:
    """What to search. Hashable -> usable as a jit-static argument.

    mode:    'full'       exact noisy MCAM search of every store row;
             'two_phase'  shortlist by ideal digital distance + exact
                          noisy rescore of the top-k candidates (the
                          production serving path);
             'ideal'      ideal-digital-distance top-k only, no rescore
                          (the cheapest serving path).
    k:       candidate count for 'two_phase' / 'ideal' (ignored by 'full').
    backend: 'auto' defers to the engine's backend; any other value
             ('ref' | 'pallas') overrides it per request.
    axes:    shard axes override; None defers to the store's own sharding
             (`MemoryStore.shard` records mesh + axes on the store).
    noisy:   per-request override of SearchConfig.noisy (None defers to
             the config). noisy=False serves the NOISELESS hardware
             forward on any mode/backend/sharding -- the serving side of
             the train/serve parity contract: noiseless votes are
             bit-identical to hardware-aware training's in-episode scores
             (`RetrievalEngine.episode_votes`) on the same support set.
    nprobe:  shards visited per query ('two_phase' / 'ideal' only). On a
             partitioned store (`MemoryStore.shard`), nprobe=p < n_shards
             engages the phase-0 router (engine/router.py): the per-shard
             summary sketch is scored with one small matmul and phase 1/2
             run only on the top-p shards -- bit-identical to brute force
             restricted to those shards (same SHORTLIST_MASK_PENALTY,
             same (distance, index) lex merge). None (the default) and
             nprobe >= n_shards reproduce the exhaustive all-shards
             search byte-for-byte. Recall-vs-nprobe is a measured serving
             knob (benchmarks/bench_router.py, BENCH_router.json).

    >>> SearchRequest(mode="ideal", k=8).mode
    'ideal'
    >>> SearchRequest().k                  # default: two-phase, k=64
    64
    >>> SearchRequest(mode="nearest")
    Traceback (most recent call last):
        ...
    ValueError: unknown search mode 'nearest'; expected one of \
('full', 'two_phase', 'ideal')
    """

    mode: str = "two_phase"
    k: int = 64
    backend: str = "auto"
    axes: tuple[str, ...] | None = None
    noisy: bool | None = None
    nprobe: int | None = None

    def __post_init__(self) -> None:
        if self.mode not in MODES:
            raise ValueError(
                f"unknown search mode {self.mode!r}; expected one of {MODES}")
        if self.nprobe is not None:
            if self.mode == "full":
                raise ValueError(
                    "SearchRequest: nprobe routes the shortlist modes "
                    "('two_phase' / 'ideal'); mode='full' scores every "
                    "row by definition")
            if self.nprobe < 1:
                raise ValueError(f"SearchRequest: nprobe must be >= 1, "
                                 f"got {self.nprobe}")


@partial(jax.tree_util.register_dataclass,
         data_fields=["votes", "dist", "indices", "labels"],
         meta_fields=["iterations"])
@dataclasses.dataclass(frozen=True)
class SearchResult:
    """One result type for every mode/backend/sharding.

    votes:      (B, K) MCAM vote scores (-inf on masked/empty candidates);
                for mode='full', K == store rows; for 'ideal', votes==-dist
                on valid candidates (and -inf on masked ones).
    dist:       (B, K) ideal digital AVSS distance. Masked rows (slots never
                written, or ragged-shard pad rows) additionally carry the
                integer-exact SHORTLIST_MASK_PENALTY (2**22, added in
                phase 1 -- in every mode, 'ideal' included), which is why
                they sort after every valid candidate while backend and
                sharding bit-parity survives masking.
    indices:    (B, K) global store rows of each candidate.
    labels:     (B, K) candidate labels (-1 on masked/empty candidates).
    iterations: word-line cycles per query (python int; static metadata).

    A tie-heavy toy result -- votes tie at 3.0, so the smaller ideal
    distance wins, and `best()` / `predict()` pick label 9:

    >>> import jax.numpy as jnp
    >>> r = SearchResult(votes=jnp.array([[1.0, 3.0, 3.0]]),
    ...                  dist=jnp.array([[0.0, 2.0, 1.0]]),
    ...                  indices=jnp.array([[0, 1, 2]]),
    ...                  labels=jnp.array([[5, 7, 9]]))
    >>> int(r.best()[0])
    2
    >>> int(r.predict()[0])
    9
    """

    votes: jax.Array
    dist: jax.Array
    indices: jax.Array
    labels: jax.Array
    iterations: int = 0

    def best(self) -> jax.Array:
        """(B,) position of the best candidate per query: max votes, vote
        ties broken exactly by ideal digital distance, then by index
        (stable argmin) -- the paper's retrieval rule."""
        with jax.named_scope(hc.ANSWER_SCOPE):
            top = self.votes.max(axis=-1, keepdims=True)
            return jnp.argmin(jnp.where(self.votes == top, self.dist,
                                        jnp.inf), axis=-1)

    def predict(self) -> jax.Array:
        """(B,) 1-NN label prediction: the label of `best()` per query.

        The -1 sentinel: a label of -1 marks a candidate from a slot that
        was never written (empty store slots, ragged-shard pad rows). Such
        candidates carry -inf votes and the SHORTLIST_MASK_PENALTY on
        their distance, so they can only win when the store holds NO valid
        candidate at all -- in that case every query predicts -1, never an
        arbitrary class label (asserted for every mode/backend/sharding in
        tests/test_store.py). Callers should treat -1 as "no prediction".

        >>> import jax.numpy as jnp
        >>> empty = SearchResult(votes=jnp.full((1, 2), -jnp.inf),
        ...                      dist=jnp.full((1, 2), 2.0 ** 22),
        ...                      indices=jnp.array([[0, 1]]),
        ...                      labels=jnp.array([[-1, -1]]))
        >>> int(empty.predict()[0])        # no valid candidate -> sentinel
        -1
        """
        with jax.named_scope(hc.ANSWER_SCOPE):
            return jnp.take_along_axis(self.labels, self.best()[:, None],
                                       1)[:, 0]

    def asdict(self) -> dict[str, jax.Array | int]:
        """Legacy result-dict view (the pre-redesign contract)."""
        return {"votes": self.votes, "dist": self.dist,
                "indices": self.indices, "labels": self.labels,
                "iterations": self.iterations}
