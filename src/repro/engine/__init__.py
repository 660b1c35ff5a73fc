"""Unified retrieval engine: one API over the ref / Pallas / MXU-LUT backends.

The serving contract (PR 2) is store-centric:

  MemoryStore          the programmed MCAM memory as an immutable registered
                       pytree -- quantized values, labels, quant range, ring
                       state, plus the WRITE-TIME `proj` (LUT projection) and
                       `s_grid` (string-grid) layouts, and its own sharding
                       (`shard(mesh, axes)` row-shards, padding ragged splits
                       with label -1 rows).
  SearchRequest        what to search: mode ('full' | 'two_phase' | 'ideal'),
                       k, backend override, shard-axes override.
  SearchResult         votes / dist / indices / labels / iterations -- one
                       typed result for every mode, backend and sharding.
  RetrievalEngine      `search(store, queries, request) -> SearchResult`, the
                       single dispatch point. The raw-array methods (`full`,
                       `two_phase`, `sharded_two_phase`) remain underneath
                       for callers without a store; all paths are
                       bit-identical (tests/test_engine.py).
  TenantStore          N per-tenant stores stacked along a leading tenant
                       axis, searched in one coalesced device batch through
                       `RetrievalEngine.search_tenants` (PR 9) -- one jit
                       cache entry for ANY tenant count, per-tenant results
                       bit-identical to solo `search` (tests/test_tenant.py).
  router / ShardPager  the memory hierarchy (PR 10): every partitioned store
                       maintains a per-shard class-centroid sketch at write
                       time; `SearchRequest.nprobe` scores it and searches
                       only the top-p shards (bit-identical to brute force
                       over the visited shards), and `ShardPager` serves a
                       `shard(n_shards=..., residency="host")` store whose
                       cold shards live in host memory, paging visited ones
                       through a fixed set of device slots.
"""

from repro.engine.api import SearchRequest, SearchResult
from repro.engine.backends import (BACKENDS, kernels_available,
                                   resolve_backend)
from repro.engine.engine import RetrievalEngine
from repro.engine.pager import ShardPager
from repro.engine.router import (ROUTER_BUCKETS, build_sketch, route_scores,
                                 sketch_centroids, top_shards)
from repro.engine.sharded import (SHORTLIST_DENSE_MAX_BYTES,
                                  sharded_ideal_search,
                                  sharded_two_phase_search)
from repro.engine.store import MemoryStore
from repro.engine.tenant import TenantStore, tenant_query_rank

__all__ = [
    "BACKENDS",
    "MemoryStore",
    "ROUTER_BUCKETS",
    "RetrievalEngine",
    "SHORTLIST_DENSE_MAX_BYTES",
    "SearchRequest",
    "SearchResult",
    "ShardPager",
    "TenantStore",
    "build_sketch",
    "kernels_available",
    "resolve_backend",
    "route_scores",
    "sharded_ideal_search",
    "sharded_two_phase_search",
    "sketch_centroids",
    "tenant_query_rank",
    "top_shards",
]
