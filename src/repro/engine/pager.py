"""ShardPager: beyond-HBM serving -- host-resident shards paged on demand.

The memory-hierarchy bottom half of ROADMAP item 2: a partitioned store
(`MemoryStore.shard(n_shards=S, residency="host")`) keeps its row blocks
in host memory, and only a small LRU working set of shard blocks lives in
device HBM. Per batch:

1. the router sketch (device-resident, tiny) is scored with one jitted
   matmul (`engine/router.route_scores`);
2. the top-`nprobe` shards per query are paged into device slot tables
   (`jax.device_put` explicit copies -- `jax.transfer_guard`-clean), LRU
   evicting cold slots;
3. ONE jitted program -- the same `_routed_block_search` core
   `RetrievalEngine.search(nprobe=p)` uses on device-resident stores --
   searches the resident tables, so the result is bit-identical to the
   routed search on a fully device-resident twin of the store
   (tests/test_pager.py), which is itself bit-identical to brute force
   restricted to the visited shards;
4. the best not-yet-resident shard (by aggregate router score) is staged
   asynchronously into a spare slot (double-buffering: on real
   accelerators the host->device copy overlaps the search dispatched in
   step 3; `slots >= nprobe + 1` leaves room for it).

Addressable capacity is host memory, not HBM: HBM holds
O(slots * rows_per_shard) plus the sketch, independent of S.

>>> import jax.numpy as jnp
>>> from repro.core.avss import SearchConfig
>>> from repro.engine import (MemoryStore, RetrievalEngine,
...                           SearchRequest)
>>> from repro.engine.pager import ShardPager
>>> cfg = SearchConfig("mtmc", cl=4, mode="avss", use_kernel="ref")
>>> vals = (jnp.arange(64).reshape(32, 2) * 3) % 10
>>> store = MemoryStore.from_quantized(vals, jnp.arange(32) % 8, cfg)
>>> req = SearchRequest(mode="two_phase", k=4, nprobe=2)
>>> pager = ShardPager(store.shard(n_shards=4, residency="host"),
...                    RetrievalEngine(cfg), slots=3)
>>> res = pager.search(jnp.array([[1, 2]]), req)
>>> ref = RetrievalEngine(cfg).search(          # device-resident twin
...     store.shard(n_shards=4), jnp.array([[1, 2]]), req)
>>> bool(jnp.array_equal(res.votes, ref.votes))
True
>>> len(pager.resident())             # the nprobe=2 visited shards
2
"""

from __future__ import annotations

from collections import OrderedDict
from functools import partial
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import router as router_lib
from repro.engine.api import SearchRequest, SearchResult
from repro.engine.engine import RetrievalEngine
from repro.engine.store import MemoryStore

#: host-side block tables a slot holds (the leaves a routed search reads).
_BLOCK_FIELDS = ("proj", "s_grid", "labels")


class ShardPager:
    """LRU pager over a host-partitioned MemoryStore (module docstring).

    store:   partitioned store (`shard(n_shards=S, ...)`, mesh-less);
             `residency="host"` is the intended placement -- the pager
             reads the blocks as host numpy views and owns all device
             placement itself.
    engine:  the RetrievalEngine whose routed-search core (and backend)
             the paged search runs.
    slots:   device-resident shard slots (default min(S, 4)). A search
             needs the BATCH's visited-shard union (at most
             `B * nprobe`, typically far fewer for correlated queries)
             to fit in `slots`; head-room beyond `nprobe` enables the
             prefetch slot.
    prefetch: stage the next-best shard after each search (step 4).
    """

    def __init__(self, store: MemoryStore, engine: RetrievalEngine,
                 slots: int | None = None, prefetch: bool = True) -> None:
        if store.mesh is not None or store.n_shards < 2:
            raise ValueError(
                "ShardPager: pass a logically partitioned store "
                "(MemoryStore.shard(n_shards=S[, residency='host'])); "
                "mesh-sharded stores are already device-resident")
        self.store = store
        self.engine = engine
        self.n_shards = store.n_shards
        self.rows = store.capacity // self.n_shards
        self.slots = min(self.n_shards, 4) if slots is None else slots
        if not 1 <= self.slots <= self.n_shards:
            raise ValueError(f"ShardPager: slots={self.slots} must be in "
                             f"[1, n_shards={self.n_shards}]")
        self.prefetch = prefetch
        self.pages_in = 0                     # host->device block copies

        # host blocks: zero-copy numpy views per leaf, (S, rows, ...)
        s = self.n_shards
        self._host = {
            f: np.asarray(getattr(store, f)).reshape(
                (s, self.rows) + np.asarray(getattr(store, f)).shape[1:])
            for f in _BLOCK_FIELDS}

        # device slot tables (m, rows, ...) + the tiny resident sketch
        dev = jax.devices()[0]
        self._tables = {
            f: jax.device_put(jnp.zeros((self.slots,) + h.shape[1:],
                                        h.dtype), dev)
            for f, h in self._host.items()}
        self._sketch = (jax.device_put(store.sketch_sums, dev),
                        jax.device_put(store.sketch_counts, dev))
        self._lru: OrderedDict[int, int] = OrderedDict()  # shard -> slot
        self._staged: dict[int, dict[str, jax.Array]] = {}

        enc = engine.cfg.enc
        self._route = jax.jit(lambda q, su, c: router_lib.route_scores(
            q, su, c, enc))

        @partial(jax.jit, static_argnames=("req",))
        def _jsearch(proj_t: jax.Array, sgrid_t: jax.Array,
                     labels_t: jax.Array, shard_of: jax.Array, q: jax.Array,
                     slot_ids: jax.Array, req: SearchRequest) -> SearchResult:
            return engine._routed_block_search(
                q, slot_ids, shard_of, proj_t, sgrid_t, labels_t, req)

        self._jsearch = _jsearch
        # slot is STATIC (at most `slots` variants) so installing pages no
        # scalar to the device -- steady-state stays transfer-guard-clean
        self._install = jax.jit(
            lambda table, block, slot: table.at[slot].set(block),
            static_argnums=2, donate_argnums=0)

    # -- residency ----------------------------------------------------------

    def resident(self) -> list[int]:
        """Currently resident shard ids, ascending."""
        return sorted(self._lru)

    def _shard_of(self) -> np.ndarray:
        """(slots,) slot -> global shard id (-1 for an empty slot)."""
        out = np.full((self.slots,), -1, np.int32)
        for shard, slot in self._lru.items():
            out[slot] = shard
        return out

    def _stage(self, shard: int) -> None:
        """Begin the (async on real backends) host->device copy of one
        shard's blocks. `jax.device_put` is an EXPLICIT transfer, so
        staging is clean under `jax.transfer_guard("disallow")`."""
        if shard in self._lru or shard in self._staged:
            return
        dev = jax.devices()[0]
        self._staged[shard] = {
            f: jax.device_put(h[shard], dev) for f, h in self._host.items()}

    def ensure(self, shard_ids: Iterable[int]) -> dict[int, int]:
        """Page the given shards in (LRU-evicting cold slots) and return
        the shard -> slot map. Raises if they cannot fit at once."""
        want = sorted(set(int(s) for s in shard_ids))
        if len(want) > self.slots:
            raise ValueError(
                f"ShardPager: {len(want)} shards requested at once but "
                f"only {self.slots} device slots (raise `slots` or lower "
                f"`nprobe`)")
        for shard in want:
            if shard in self._lru:
                self._lru.move_to_end(shard)
                continue
            if len(self._lru) < self.slots:
                slot = len(self._lru)
            else:
                # evict the least-recently-used shard NOT in this
                # working set (the `want` set fits, so one exists)
                victim = next(s for s in self._lru if s not in want)
                slot = self._lru.pop(victim)
            blocks = self._staged.pop(shard, None)
            if blocks is None:
                dev = jax.devices()[0]
                blocks = {f: jax.device_put(h[shard], dev)
                          for f, h in self._host.items()}
            for f, block in blocks.items():
                self._tables[f] = self._install(self._tables[f], block,
                                                int(slot))
            self._lru[shard] = slot
            self.pages_in += 1
        return {s: self._lru[s] for s in want}

    # -- search --------------------------------------------------------------

    def search(self, queries: jax.Array,
               request: SearchRequest) -> SearchResult:
        """Routed search over the paged store -- bit-identical to
        `RetrievalEngine.search(device_twin, queries, request)` with the
        same nprobe (tests/test_pager.py)."""
        p = request.nprobe
        if p is None or not 1 <= p <= self.n_shards:
            raise ValueError(
                f"ShardPager.search: request.nprobe must be in "
                f"[1, n_shards={self.n_shards}], got {p}")
        if p > self.slots:
            raise ValueError(f"ShardPager.search: nprobe={p} exceeds the "
                             f"{self.slots} device slots")
        dev = jax.devices()[0]
        q = jax.device_put(self.store.quantize_queries(queries), dev)
        scores = np.asarray(jax.device_get(
            self._route(q, *self._sketch)))            # (B, S) on host
        # same selection rule as router.top_shards: smallest score first,
        # ties to the lowest shard id, then ascending shard id per query
        order = np.argsort(scores, axis=1, kind="stable")
        visited = np.sort(order[:, :p], axis=1)        # (B, p) shard ids
        slot_map = self.ensure(np.unique(visited))
        slot_ids = jax.device_put(
            np.vectorize(slot_map.__getitem__)(visited).astype(np.int32),
            dev)
        shard_of = jax.device_put(self._shard_of(), dev)
        res = self._jsearch(
            self._tables["proj"], self._tables["s_grid"],
            self._tables["labels"], shard_of, q, slot_ids, request)
        if self.prefetch and p < self.n_shards and len(self._staged) < 2:
            # double-buffer: while the search above executes, stage the
            # (p+1)-th-best shard by aggregate score rank across the batch
            candidates = order[:, p]
            nxt = int(np.bincount(candidates,
                                  minlength=self.n_shards).argmax())
            self._stage(nxt)
        return res
