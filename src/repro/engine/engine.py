"""The RetrievalEngine: one `search()` entry point, one typed contract.

`search(store, queries, SearchRequest) -> SearchResult` subsumes every
retrieval path in the framework: full exact search, the two-phase
shortlist+rescore serving pipeline, and the cheap ideal-distance path --
unsharded or row-sharded (the store carries its own mesh/axes, see
repro/engine/store.py). The pre-redesign methods (`full`, `two_phase`,
`sharded_two_phase`) remain as the raw-array layer underneath and for
callers that do not hold a MemoryStore.

All backends share one semantics contract (kernels/ref.py): for a given
(SearchConfig, query batch, support store) the votes and distances are
bit-identical regardless of backend or sharding. Two facts make this cheap
to guarantee:

* Phase-1 shortlist distances are integer-valued: AVSS LUT entries are small
  integers, query one-hots are 0/1, and every backend accumulates in f32
  (exact for integers < 2**24), so the shortlist distance is the same exact
  float no matter how the reduction is ordered or which unit computes it.
* Phase-2 noise is a counter-based hash of ABSOLUTE (query, string, cell)
  coordinates, so the noisy rescore of a support does not depend on which
  shard or kernel tile evaluates it.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import TYPE_CHECKING, Any, Hashable, Sequence

import jax
import jax.numpy as jnp

from repro.analysis import hlo_contracts as hc
from repro.core import avss as avss_lib
from repro.core import encodings as enc_lib
from repro.core import mcam as mcam_lib
from repro.core import quantization as quant_lib
from repro.core.avss import SearchConfig
from repro.engine.api import SearchRequest, SearchResult
from repro.engine.backends import resolve_backend
from repro.kernels import ref as ref_kernels

if TYPE_CHECKING:
    from jax.sharding import Mesh

    from repro.engine.store import MemoryStore
    from repro.engine.tenant import TenantStore


def _noise_stream(key: jax.Array | int | None) -> jax.Array | None:
    """Fold a PRNG key (typed or legacy uint32), array or int into one
    uint32 noise-stream coordinate for the counter-based hardware noise.
    None passes through -- the stream-less coordinates are EXACTLY the
    serving ones, so episode_votes(key=None) is bit-identical to the
    noisy `full` search."""
    if key is None:
        return None
    if isinstance(key, jax.Array) and jnp.issubdtype(key.dtype,
                                                    jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    arr = jnp.atleast_1d(jnp.asarray(key)).astype(jnp.uint32).ravel()
    s = jnp.uint32(0x9E3779B9)
    for i in range(arr.shape[0]):
        s = mcam_lib._mix(s ^ arr[i])
    return s

@dataclasses.dataclass(frozen=True)
class RetrievalEngine:
    """Dispatches AVSS/SVSS searches to a selected backend.

    cfg:      the end-to-end search configuration (encoding, MCAM physics,
              noise). `cfg.use_kernel` is honoured as a fallback preference.
    backend:  'auto' | 'ref' | 'pallas' | 'mxu' | 'fused'; overrides
              cfg.use_kernel when not 'auto'.
    fused_min_rows: optional row count from which shortlists run the
              fused Pallas kernel (per shard-local block on sharded
              stores). None leaves phase 1 to the shape rule
              (`sharded._use_fused`): the dense XLA route while its
              (B, N) f32 block fits `sharded.SHORTLIST_DENSE_MAX_BYTES`,
              the kernel beyond. 'fused' always fuses and 'ref' never
              does. `SearchRequest.fused_min_rows` overrides this per
              request.
    """

    cfg: SearchConfig
    backend: str = "auto"
    fused_min_rows: int | None = None

    @property
    def resolved_backend(self) -> str:
        return resolve_backend(self.backend, self.cfg.use_kernel)

    def _cached_replace(self, key: Hashable,
                        **changes: Any) -> "RetrievalEngine":
        """dataclasses.replace cached per instance: per-request overrides
        return the SAME engine object on every call -- no rebuild, and
        closures keyed on the engine (jit caches) keep hitting."""
        cache = self.__dict__.get("_backend_cache")
        if cache is None:
            cache = {}
            object.__setattr__(self, "_backend_cache", cache)
        eng = cache.get(key)
        if eng is None:
            eng = dataclasses.replace(self, **changes)
            cache[key] = eng
        return eng

    def with_backend(self, backend: str) -> "RetrievalEngine":
        """Engine with a per-request backend override (cached, see
        `_cached_replace`); 'auto' and the current backend return self."""
        if backend in ("auto", self.backend):
            return self
        return self._cached_replace(backend, backend=backend)

    def with_noisy(self, noisy: bool | None) -> "RetrievalEngine":
        """Engine whose SearchConfig has `noisy` overridden (cached); None
        and the current setting return self. This is what threads
        `SearchRequest.noisy` through every mode/backend/sharding -- e.g.
        serving a noiseless forward for a train/serve parity check without
        rebuilding configs."""
        if noisy is None or noisy == self.cfg.noisy:
            return self
        return self._cached_replace(
            ("noisy", noisy), cfg=dataclasses.replace(self.cfg, noisy=noisy))

    def _fused_threshold(self, request: SearchRequest | None = None
                         ) -> int | None:
        """Effective fused-shortlist row threshold: the request override
        when set, else this engine's `fused_min_rows` (None: the shape
        rule alone decides)."""
        if request is not None and request.fused_min_rows is not None:
            return request.fused_min_rows
        return self.fused_min_rows

    # -- unified entry point -----------------------------------------------

    def search(self, store: MemoryStore, queries: jax.Array,
               request: SearchRequest | None = None) -> SearchResult:
        """Search a programmed MemoryStore: the one serving entry point.

        store:    repro.engine.store.MemoryStore. Its write-time `proj` and
                  `s_grid` layouts are used directly, so nothing re-runs
                  `layout_support`/`support_projection` under jit; its
                  (mesh, axes) metadata selects the sharded dispatch.
        queries:  (B, dim) float embeddings (quantized with the store's
                  calibrated range) or pre-quantized ints (passed through).
        request:  SearchRequest (mode, k, backend, axes, fused threshold);
                  default two-phase.

        Results are bit-identical to the raw-array methods below for every
        mode/backend/sharding (tests/test_engine.py, tests/test_store.py)
        -- including whether a shortlist ran the dense XLA dot + top_k or
        the fused Pallas kernel (the dispatch is purely a perf decision).

        >>> import jax.numpy as jnp
        >>> from repro.core.avss import SearchConfig
        >>> from repro.engine import (MemoryStore, RetrievalEngine,
        ...                           SearchRequest)
        >>> cfg = SearchConfig("mtmc", cl=4, mode="avss", use_kernel="ref")
        >>> sv = jnp.array([[0, 3], [5, 5], [9, 7]])   # quantized supports
        >>> store = MemoryStore.from_quantized(sv, jnp.array([7, 8, 9]), cfg)
        >>> res = RetrievalEngine(cfg).search(          # query words in [0,4)
        ...     store, jnp.array([[1, 1]]), SearchRequest(mode="ideal", k=1))
        >>> res.predict().tolist()          # nearest support is row 1
        [8]
        """
        req = request if request is not None else SearchRequest()
        eng = self.with_backend(req.backend).with_noisy(req.noisy)
        if store.residency == "host":
            raise ValueError(
                "RetrievalEngine.search: this store's shards live in host "
                "memory (shard(..., residency='host')); search it through "
                "repro.engine.pager.ShardPager, which pages the visited "
                "shards into device memory -- or re-shard with "
                "residency='device'.")
        q = store.quantize_queries(queries)
        iters = eng._iterations(q.shape[-1])

        # phase-0 routing: engaged iff the request asks for FEWER shards
        # than the store has (nprobe=None and nprobe >= n_shards fall
        # through to the exhaustive paths below, byte-for-byte)
        if (req.nprobe is not None and req.mode != "full"
                and req.nprobe < store.n_shards):
            return eng._search_routed(store, q, req)

        if store.mesh is None or req.mode == "full":
            return eng._search_unsharded(store, q, req)

        # per-shard shortlists share the unsharded dispatch rule, on each
        # shard's LOCAL (B, N_loc) block (engine/sharded._use_fused)
        axes = req.axes if req.axes is not None else store.axes
        fmr = eng._fused_threshold(req)
        backend = eng.resolved_backend
        if req.mode == "two_phase":
            from repro.engine import sharded
            with jax.named_scope(hc.SHORTLIST_SCOPE):
                valid = store.valid
            res = sharded.sharded_two_phase_search(
                q, store.values, eng.cfg, store.mesh, axes=axes,
                k=req.k, valid=valid, labels=store.labels,
                s_grid=store.s_grid, proj=store.proj,
                packed=store.proj_packed, pack_bits=store.pack_bits,
                backend=backend, fused_min_rows=fmr)
            # labels come from the per-shard fold (-1 on empty/pad
            # rows): mask their votes without any global gather
            with jax.named_scope(hc.ANSWER_SCOPE):
                votes = jnp.where(res["labels"] >= 0, res["votes"],
                                  -jnp.inf)
            return SearchResult(votes, res["dist"], res["indices"],
                                res["labels"], res["iterations"])
        from repro.engine import sharded
        from repro.kernels import ops as kernel_ops
        with jax.named_scope(hc.SHORTLIST_SCOPE):
            q1h = kernel_ops.query_onehot(q, jnp.float32)
        res = sharded.sharded_ideal_search(
            q1h, store.proj, store.labels, store.mesh, axes=axes,
            k=req.k, backend=backend, fused_min_rows=fmr,
            packed=store.proj_packed, pack_bits=store.pack_bits,
            enc=eng.cfg.enc)
        with jax.named_scope(hc.ANSWER_SCOPE):
            votes = jnp.where(res["labels"] >= 0, res["votes"], -jnp.inf)
        return SearchResult(votes, res["dist"], res["indices"],
                            res["labels"], iters)

    def _search_unsharded(self, store: MemoryStore, q: jax.Array,
                          req: SearchRequest,
                          noise_qidx: jax.Array | None = None,
                          batch: int | None = None) -> SearchResult:
        """The unsharded (single-block) search body shared by `search` and
        `search_tenants`: `self` must already carry the request's backend
        and noisy overrides, `q` is already quantized. `noise_qidx` (B,)
        overrides the per-query noise coordinates (see `full`); `search`
        leaves it None (arange(B)), `search_tenants` passes each query's
        rank within its tenant group so the vmapped dispatch is
        bit-identical to per-tenant solo calls. `batch` is the query count
        of the whole dense block the shortlist dispatch sizes (default
        q's; `search_tenants` vmaps this body one query at a time)."""
        from repro.engine.sharded import _local_shortlist, _use_fused
        with jax.named_scope(hc.SHORTLIST_SCOPE):   # phase 1's row mask
            valid = store.valid
        iters = self._iterations(q.shape[-1])
        if req.mode == "full":
            res = self.full(q, store.values, s_grid=store.s_grid,
                            noise_qidx=noise_qidx)
            with jax.named_scope(hc.ANSWER_SCOPE):
                votes = jnp.where(store.valid[None, :], res["votes"],
                                  -jnp.inf)
                indices = jnp.broadcast_to(
                    jnp.arange(store.capacity, dtype=jnp.int32),
                    votes.shape)
                labels = jnp.broadcast_to(store.labels, votes.shape)
            return SearchResult(votes, res["dist"], indices, labels,
                                res["iterations"])
        fused = _use_fused(self.resolved_backend, batch or q.shape[0],
                           store.capacity, self._fused_threshold(req))
        if req.mode == "two_phase":
            res = self.two_phase(q, store.values, k=req.k, valid=valid,
                                 s_grid=store.s_grid, proj=store.proj,
                                 packed=store.proj_packed,
                                 pack_bits=store.pack_bits, fused=fused,
                                 noise_qidx=noise_qidx)
            with jax.named_scope(hc.ANSWER_SCOPE):
                labels = store.labels[res["indices"]]  # -1 on empty slots
                votes = jnp.where(labels >= 0, res["votes"], -jnp.inf)
            return SearchResult(votes, res["dist"], res["indices"], labels,
                                res["iterations"])
        # ideal: top-k by the exact integer-valued digital distance against
        # the write-time LUT projection. Masked rows carry the integer-exact
        # SHORTLIST_MASK_PENALTY (the same contract as two_phase / the
        # sharded ideal path), so both routes below are bit-identical: the
        # dense XLA dot + top_k while its (B, N) block fits the budget, the
        # fused Pallas kernel (HBM O(B*k + N*4d)) beyond it.
        from repro.kernels import ops as kernel_ops
        k = min(req.k, store.capacity)
        with jax.named_scope(hc.SHORTLIST_SCOPE):
            if fused:
                dist, idx = kernel_ops.lut_shortlist(
                    q, store.values, self.cfg.enc, k, valid=valid,
                    proj=store.proj, packed=store.proj_packed,
                    pack_bits=store.pack_bits)
            else:
                q1h = kernel_ops.query_onehot(q, store.proj.dtype)
                dist, idx = _local_shortlist(q1h, store.proj, valid, k,
                                             fused=False)
        with jax.named_scope(hc.ANSWER_SCOPE):
            labels = store.labels[idx]
            votes = jnp.where(labels >= 0, -dist, -jnp.inf)
        return SearchResult(votes, dist, idx, labels, iters)

    # -- routed (phase-0) search -------------------------------------------

    def _search_routed(self, store: MemoryStore, q: jax.Array,
                       req: SearchRequest) -> SearchResult:
        """nprobe-routed search over a partitioned store: score the
        write-time router sketch (engine/router.py, one small matmul under
        the "router_sketch" scope), then run phase 1/2 on the top-p shard
        blocks only -- bit-identical to brute force restricted to the
        visited shards (tests/test_router.py). `self` already carries the
        request's backend/noisy overrides; `q` is already quantized."""
        from repro.engine import router as router_lib
        s = store.n_shards
        rows = store.capacity // s
        # the visited blocks' gathers stay in this layer; phase 1 and 2
        # inside them take their own scopes
        with jax.named_scope(hc.ROUTE_SCOPE):
            scores = router_lib.route_scores(q, store.sketch_sums,
                                             store.sketch_counts,
                                             self.cfg.enc)
            sids = router_lib.top_shards(scores, int(req.nprobe or 0))
            # per-shard block tables (S, rows, ...); on a mesh-sharded
            # store these reshapes stay sharded and XLA inserts the
            # per-query block gathers (the single-device / logical-partition
            # path is the one the routed contract cells pin collective-free)
            packed_t = (None if store.proj_packed is None
                        else store.proj_packed.reshape(s, rows, -1))
            return self._routed_block_search(
                q, sids, jnp.arange(s, dtype=jnp.int32),
                store.proj.reshape(s, rows, -1), packed_t,
                store.s_grid.reshape((s, rows) + store.s_grid.shape[1:]),
                store.labels.reshape(s, rows), req, store.pack_bits)

    def _routed_block_search(self, q: jax.Array, slot_ids: jax.Array,
                             shard_of: jax.Array, proj_t: jax.Array,
                             packed_t: jax.Array | None,
                             sgrid_t: jax.Array, labels_t: jax.Array,
                             req: SearchRequest, pack_bits: int,
                             noise_qidx: jax.Array | None = None
                             ) -> SearchResult:
        """Shared routed-search core over per-shard block tables.

        `search` calls it with the store's own (S, rows, ...) tables and
        `shard_of = arange(S)`; `engine/pager.ShardPager` calls it with
        its device-RESIDENT slot tables (M, rows, ...) and the slot ->
        global-shard map. Per query, `slot_ids` (B, p) names the visited
        table rows ORDERED BY ASCENDING GLOBAL SHARD ID -- concatenating
        the blocks in that order makes the candidate axis globally
        index-ordered, so the shared `_local_shortlist` (fused kernel or
        dense matmul, same mask penalty) reproduces the exhaustive
        search's (distance, global index) lex order exactly on the
        visited subset. Phase 2 rescores with GLOBAL indices feeding the
        noise counters, so routed votes equal the full search's votes for
        every shortlisted candidate.
        """
        from repro.engine.sharded import _local_shortlist, _use_fused
        from repro.kernels import ops as kernel_ops
        cfg = self.cfg
        assert cfg.mode == "avss", "routed search shortlists the AVSS LUT"
        p = slot_ids.shape[1]
        rows = proj_t.shape[1]
        rows_vis = p * rows
        k = min(req.k, rows_vis)
        fused = _use_fused(self.resolved_backend, q.shape[0], rows_vis,
                           self._fused_threshold(req))
        two_phase = req.mode == "two_phase"
        with jax.named_scope(hc.SHORTLIST_SCOPE):
            q1h = kernel_ops.query_onehot(q, jnp.float32)
        with jax.named_scope(hc.RESCORE_SCOPE):
            q_grid = avss_lib.layout_query(q, cfg.enc, "avss",
                                           cfg.mcam.string_len)
            weights = cfg.enc.weights_array()
            thresholds = jnp.asarray(cfg.mcam.thresholds())
        if noise_qidx is None:
            noise_qidx = jnp.arange(q.shape[0], dtype=jnp.uint32)

        def one(q1h_b: jax.Array, qgrid_b: jax.Array, sl_b: jax.Array,
                qi_b: jax.Array
                ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
            lab_vis = labels_t[sl_b].reshape(rows_vis)
            proj_vis = proj_t[sl_b].reshape(rows_vis, -1)
            pk_vis = (packed_t[sl_b].reshape(rows_vis, -1)
                      if fused and packed_t is not None else None)
            dist, li = _local_shortlist(q1h_b[None], proj_vis,
                                        lab_vis >= 0, k, fused=fused,
                                        packed=pk_vis, pack_bits=pack_bits)
            # local candidate position -> global store row: visited blocks
            # are ascending-shard-ordered, block i covers global rows
            # [shard_of[sl_b[i]] * rows, ...)
            gidx = shard_of[sl_b][li // rows] * rows + li % rows
            if two_phase:
                with jax.named_scope(hc.RESCORE_SCOPE):
                    sg_vis = sgrid_t[sl_b].reshape((rows_vis,)
                                                   + sgrid_t.shape[2:])
                    votes = kernel_ops.rescore_shortlist(
                        qgrid_b[None], sg_vis, li, weights, cfg,
                        thresholds, noise_idx=gidx, noise_qidx=qi_b[None])
            else:
                votes = -dist
            with jax.named_scope(hc.ANSWER_SCOPE):
                lab = lab_vis[li]
                votes = jnp.where(lab >= 0, votes, -jnp.inf)
            return votes[0], dist[0], gidx[0], lab[0]

        votes, dist, indices, labels = jax.vmap(one)(
            q1h, q_grid, slot_ids, noise_qidx.astype(jnp.uint32))
        return SearchResult(votes, dist, indices, labels,
                            self._iterations(q.shape[-1]))

    # -- multi-tenant dispatch ---------------------------------------------

    def search_tenants(self, tstore: TenantStore, queries: jax.Array,
                       tenant_ids: jax.Array,
                       request: SearchRequest | None = None) -> SearchResult:
        """One compiled search over a batch of queries from MANY tenants.

        tstore:     repro.engine.tenant.TenantStore -- N per-tenant
                    MemoryStores stacked along a leading tenant axis.
        queries:    (B, dim) float embeddings (quantized per query against
                    the OWNING tenant's calibrated range) or pre-quantized
                    ints (passed through).
        tenant_ids: (B,) int -- the owning tenant of each query. Traced
                    data, NOT static: batches with different tenant mixes
                    hit the same compiled program (one jit cache entry per
                    tenant count/batch shape, asserted by the
                    `single_jit_entry_across_tenants` contract cell).
        request:    SearchRequest; `mode`/`backend`/`k`/`fused_min_rows`/
                    `noisy` all apply (axes is meaningless here -- tenant
                    stacks are unsharded).

        Dispatch: gather each query's tenant leaves out of the stacked
        store and vmap the single-query unsharded search over the batch --
        full/two_phase/ideal x ref/mxu/fused (the Pallas kernels batch
        under vmap), bit-identical per tenant to solo `engine.search` on
        `tstore.tenant(i)` for queries grouped in batch order (the noise
        coordinates are each query's rank within its tenant group, exactly
        the solo batch positions; tests/test_tenant.py). Results span the
        stack's padded capacity: a ragged tenant's pad rows behave like
        never-written slots (-inf votes, label -1, mask penalty).

        >>> import jax.numpy as jnp
        >>> from repro.core.avss import SearchConfig
        >>> from repro.engine import (MemoryStore, RetrievalEngine,
        ...                           SearchRequest, TenantStore)
        >>> cfg = SearchConfig("mtmc", cl=4, mode="avss", use_kernel="ref")
        >>> a = MemoryStore.from_quantized(
        ...     jnp.array([[0, 3], [9, 7]]), jnp.array([1, 2]), cfg)
        >>> b = MemoryStore.from_quantized(
        ...     jnp.array([[5, 5]]), jnp.array([7]), cfg)
        >>> res = RetrievalEngine(cfg).search_tenants(
        ...     TenantStore.stack([a, b]), jnp.array([[3, 2], [3, 2]]),
        ...     jnp.array([0, 1]), SearchRequest(mode="ideal", k=1))
        >>> res.predict().tolist()     # same query, per-tenant answers
        [2, 7]
        """
        from repro.engine import tenant as tenant_lib
        req = request if request is not None else SearchRequest()
        eng = self.with_backend(req.backend).with_noisy(req.noisy)
        tenant_ids = jnp.asarray(tenant_ids).astype(jnp.int32)
        q = tstore.quantize_queries(queries, tenant_ids)
        rank = tenant_lib.tenant_query_rank(tenant_ids)
        view = tstore.query_view(tenant_ids)

        def one(store_b: MemoryStore, q_b: jax.Array,
                rank_b: jax.Array) -> SearchResult:
            return eng._search_unsharded(store_b, q_b[None], req,
                                         noise_qidx=rank_b[None],
                                         batch=q.shape[0])

        res = jax.vmap(one)(view, q, rank)
        # drop the inner singleton query axis: (B, 1, K) -> (B, K)
        return SearchResult(res.votes[:, 0], res.dist[:, 0],
                            res.indices[:, 0], res.labels[:, 0],
                            res.iterations)

    # -- differentiable episodic forward (hardware-aware training) ---------

    def episode_votes(self, q_emb: jax.Array, s_emb: jax.Array, *,
                      clip_std: float = 2.5, sa_tau: float = 0.02,
                      key: jax.Array | int | None = None,
                      noisy: bool | None = None,
                      rng_range: tuple[jax.Array, jax.Array] | None = None
                      ) -> dict[str, jax.Array]:
        """Differentiable end-to-end MCAM forward on FLOAT embeddings.

        This is the training twin of `search(mode='full')`: asymmetric
        STE fake-quant, STE word encoding, the write-time string layout,
        and the `votes_from_mismatch` physics -- the SAME shared functions
        the serving path traces, with the straight-through estimators
        (`quantization.ste_round`, `encodings.encode_words_ste`,
        `mcam.ste_step`) wrapped AROUND them rather than re-implemented.
        Consequence (the train/serve parity contract,
        tests/test_train_serve_parity.py): given the same embeddings and
        quantization range, the returned votes/dist are BIT-IDENTICAL to
        `search` on a store programmed with the same supports -- noiseless,
        and even noisy when `key=None` (the counter-based noise then uses
        exactly the serving coordinates).

        q_emb (B, dim), s_emb (N, dim): float controller outputs.
        clip_std:  std-clipping for the shared quantization range.
        sa_tau:    sigmoid-STE temperature of the sense-amp step.
        key:       optional PRNG key / int folded into an extra noise-
                   stream coordinate (fresh hardware noise per train step);
                   None reproduces the serving noise exactly.
        noisy:     overrides cfg.noisy when not None.
        rng_range: optional explicit (lo, hi) quantization range, e.g. a
                   MemoryStore's calibrated range.
        Returns {votes (B, N), dist (B, N), iterations}.

        >>> import jax, jax.numpy as jnp
        >>> from repro.core.avss import SearchConfig
        >>> from repro.core.memory import MemoryConfig
        >>> from repro.engine import (MemoryStore, RetrievalEngine,
        ...                           SearchRequest)
        >>> cfg = SearchConfig("mtmc", cl=4, mode="avss", use_kernel="ref")
        >>> eng = RetrievalEngine(cfg)
        >>> s = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(0), (6, 8)))
        >>> q = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(1), (3, 8)))
        >>> votes = eng.episode_votes(q, s, noisy=False)["votes"]
        >>> store = MemoryStore.create(
        ...     MemoryConfig(capacity=6, dim=8, search=cfg)
        ... ).calibrate(jnp.concatenate([s.ravel(), q.ravel()]))
        >>> store = store.write(s, jnp.arange(6))
        >>> res = eng.search(store, q, SearchRequest(mode="full",
        ...                                          noisy=False))
        >>> bool(jnp.array_equal(votes, res.votes))   # train == serve
        True
        """
        cfg = self.cfg
        enc = cfg.enc
        sl = cfg.mcam.string_len
        if cfg.mode == "avss":
            q, v = quant_lib.quantize_asymmetric(
                q_emb, s_emb, enc.levels, clip_std, 4, rng=rng_range)
        else:
            v, _, rng = quant_lib.fake_quant(
                s_emb, quant_lib.QuantSpec(enc.levels, clip_std), rng_range)
            q, _, _ = quant_lib.fake_quant(
                q_emb, quant_lib.QuantSpec(enc.levels, clip_std), rng)
        s_grid = avss_lib.layout_support_words(
            enc_lib.encode_words_ste(v, enc), sl)          # (N, seg, L, sl)
        if cfg.mode == "avss":
            q_grid = avss_lib.layout_query(q, enc, "avss", sl)
        else:
            q_grid = avss_lib.layout_support_words(
                enc_lib.encode_words_ste(q, enc), sl)
        mm = jnp.abs(q_grid[:, None] - s_grid[None])   # (B, N, seg, L, sl)
        qidx = jnp.arange(q_emb.shape[0],
                          dtype=jnp.uint32)[:, None, None, None]
        votes, dist = avss_lib.votes_from_mismatch(
            mm, qidx, enc.weights_array(), cfg,
            jnp.asarray(cfg.mcam.thresholds()), noisy=noisy,
            noise_stream=_noise_stream(key),
            step_fn=lambda x: mcam_lib.ste_step(x, sa_tau))
        return {"votes": votes, "dist": dist,
                "iterations": self._iterations(q_emb.shape[-1])}

    def episode_scores(self, q_emb: jax.Array, s_emb: jax.Array,
                       s_labels: jax.Array, n_classes: int, *,
                       clip_std: float = 2.5, sa_tau: float = 0.02,
                       key: jax.Array | int | None = None,
                       noisy: bool | None = None,
                       rng_range: tuple[jax.Array, jax.Array] | None = None
                       ) -> jax.Array:
        """Per-class episodic logits (B, n_classes): `episode_votes`
        aggregated by `avss.class_mean_votes` -- the head HAT's CE loss
        trains and the served evaluation reuses (examples/fsl_omniglot.py,
        launch/train.py --hat)."""
        votes = self.episode_votes(
            q_emb, s_emb, clip_std=clip_std, sa_tau=sa_tau, key=key,
            noisy=noisy, rng_range=rng_range)["votes"]
        return avss_lib.class_mean_votes(votes, s_labels, n_classes)

    # -- phase-0 helpers ---------------------------------------------------

    def _grids(self, q_values: jax.Array, s_values: jax.Array,
               s_grid: jax.Array | None = None
               ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        cfg = self.cfg
        enc = cfg.enc
        sl = cfg.mcam.string_len
        if s_grid is None:                 # read-time layout (raw-array API)
            s_grid = avss_lib.layout_support(s_values, enc, sl)
        q_grid = avss_lib.layout_query(q_values, enc, cfg.mode, sl)
        return q_grid, s_grid, enc.weights_array(), \
            jnp.asarray(cfg.mcam.thresholds())

    def _iterations(self, d: int) -> int:
        cfg = self.cfg
        return avss_lib.search_iterations(d, cfg.enc, cfg.mode,
                                          cfg.mcam.string_len)

    # -- full exact search -------------------------------------------------

    def full(self, q_values: jax.Array, s_values: jax.Array, *,
             s_grid: jax.Array | None = None,
             noise_qidx: jax.Array | None = None) -> dict[str, jax.Array]:
        """Exact noisy MCAM search of every store row.

        q_values: (B, d) ints -- in [0, 4) for AVSS, [0, levels) for SVSS.
        s_values: (N, d) ints in [0, levels).
        s_grid:   optional write-time string grid (MemoryStore.s_grid);
                  when omitted the layout is computed here, read-time.
        noise_qidx: optional (B,) per-query noise coordinates (default
                  arange(B), the batch position). `search_tenants` passes
                  each query's rank within its tenant group so batched and
                  solo noisy searches agree bit-for-bit.
        Returns {votes (B, N), dist (B, N), iterations}.
        """
        cfg = self.cfg
        with jax.named_scope(hc.RESCORE_SCOPE):
            q_grid, s_grid, weights, thresholds = self._grids(
                q_values, s_values, s_grid)
            if noise_qidx is None:
                noise_qidx = jnp.arange(q_grid.shape[0], dtype=jnp.uint32)
            if self.resolved_backend == "ref":
                fn = partial(avss_lib._search_one_query, weights=weights,
                             cfg=cfg, thresholds=thresholds)
                votes, dist = jax.lax.map(
                    lambda args: fn(args[0], s_grid, args[1]),
                    (q_grid, noise_qidx.astype(jnp.uint32)),
                    batch_size=min(cfg.query_chunk, q_grid.shape[0]))
            else:  # pallas / mxu / fused all use the fused VPU search kernel
                from repro.kernels import ops as kernel_ops
                votes, dist = kernel_ops.mcam_search(
                    q_grid, s_grid, weights, cfg, thresholds,
                    qidx=noise_qidx)
        return {"votes": votes, "dist": dist,
                "iterations": self._iterations(q_values.shape[-1])}

    # -- phase-1 shortlist -------------------------------------------------

    def shortlist(self, q_values: jax.Array, s_values: jax.Array, k: int,
                  valid: jax.Array | None = None,
                  proj: jax.Array | None = None,
                  packed: jax.Array | None = None,
                  pack_bits: int | None = None,
                  fused: bool | None = None
                  ) -> tuple[jax.Array, jax.Array]:
        """Top-k supports by ideal digital AVSS distance.

        Returns (dist (B, k), indices (B, k)), ranked by (distance, index)
        lexicographically ascending -- identical across backends, including
        tie handling (distances are integer-valued, see module docstring).

        valid: optional (N,) bool mask; masked rows get the integer-exact
        SHORTLIST_MASK_PENALTY added to their distance, so they rank after
        every valid row (and keep their relative order, preserving backend
        and sharding bit-parity). Their returned dist includes the penalty.

        proj: optional write-time LUT projection (MemoryStore.proj) for the
        kernel backends; identical to recomputing it from s_values (the
        projection is a deterministic function of the values), just hoisted
        out of the search. The ref backend always recomputes -- it is the
        readable reference, and its distances are bit-identical anyway.

        packed: optional bit-packed projection (MemoryStore.proj_packed);
        the fused kernel then streams the 4-8x smaller int32 operand
        instead of `proj`, bit-identically (kernels/shortlist.py).
        pack_bits: the width `packed` was packed with (MemoryStore
        .pack_bits); required whenever `packed` is given without the
        matching `proj` (the width depends on the packing dtype).

        fused: run the fused Pallas kernel (True) or the dense route
        (False); None applies the dispatch rule every shortlist site
        shares (`sharded._use_fused`) to this call's (B, N) with the
        engine's `fused_min_rows`. The dense route on a kernel backend is
        the same XLA dot against `proj` + lax.top_k as every other site
        (`sharded._local_shortlist`); 'ref' keeps its readable
        recomputation from s_values.
        """
        from repro.engine.sharded import _local_shortlist, _use_fused
        from repro.kernels import ops as kernel_ops
        cfg = self.cfg
        assert cfg.mode == "avss", "shortlists use the AVSS LUT"
        k = min(k, s_values.shape[0])
        backend = self.resolved_backend
        if fused is None:
            fused = _use_fused(backend, q_values.shape[0], s_values.shape[0],
                               self.fused_min_rows)
        with jax.named_scope(hc.SHORTLIST_SCOPE):
            if fused:
                return kernel_ops.lut_shortlist(
                    q_values, s_values, cfg.enc, k, valid=valid, proj=proj,
                    packed=packed, pack_bits=pack_bits)
            if backend != "ref":
                if proj is None:
                    proj = kernel_ops.support_projection(s_values, cfg.enc)
                q1h = kernel_ops.query_onehot(q_values, proj.dtype)
                return _local_shortlist(q1h, proj, valid, k, fused=False)
            lut = jnp.asarray(enc_lib.avss_sum_lut(cfg.enc), jnp.float32)
            dist = ref_kernels.avss_dist_ref(q_values, s_values, lut)
            if valid is not None:
                dist = dist + jnp.where(
                    valid, 0.0, kernel_ops.SHORTLIST_MASK_PENALTY)[None]
            neg, idx = jax.lax.top_k(-dist, k)
            return -neg, idx

    # -- two-phase search --------------------------------------------------

    def two_phase(self, q_values: jax.Array, s_values: jax.Array,
                  k: int = 64, valid: jax.Array | None = None, *,
                  s_grid: jax.Array | None = None,
                  proj: jax.Array | None = None,
                  packed: jax.Array | None = None,
                  pack_bits: int | None = None,
                  fused: bool | None = None,
                  noise_qidx: jax.Array | None = None
                  ) -> dict[str, jax.Array]:
        """Shortlist + exact noisy rescore (beyond-paper TPU pipeline).

        s_grid / proj: optional write-time layouts (MemoryStore fields);
        omitted -> recomputed here, read-time, with identical results.
        fused: phase-1 route (see `shortlist`); None applies the dispatch
        rule.
        noise_qidx: optional (B,) per-query noise coordinates for the
        rescore (see `full`); default arange(B).
        Returns {votes (B, k), dist (B, k) ideal shortlist distances
        (masked rows carry the mask penalty), indices (B, k) global support
        rows, iterations}. Votes are bit-identical to `full` for every
        support that makes the shortlist.
        """
        from repro.kernels import ops as kernel_ops
        cfg = self.cfg
        dist, idx = self.shortlist(q_values, s_values, k, valid=valid,
                                   proj=proj, packed=packed,
                                   pack_bits=pack_bits, fused=fused)
        with jax.named_scope(hc.RESCORE_SCOPE):
            q_grid, s_grid, weights, thresholds = self._grids(
                q_values, s_values, s_grid)
            votes = kernel_ops.rescore_shortlist(
                q_grid, s_grid, idx, weights, cfg, thresholds,
                noise_qidx=noise_qidx)
        return {"votes": votes, "dist": dist, "indices": idx,
                "iterations": self._iterations(q_values.shape[-1])}

    # -- sharded two-phase search -------------------------------------------

    def sharded_two_phase(self, q_values: jax.Array, s_values: jax.Array,
                          mesh: Mesh, axes: Sequence[str] = ("data",),
                          k: int = 64, valid: jax.Array | None = None
                          ) -> dict[str, jax.Array]:
        """Two-phase search with the store row-sharded over mesh `axes`.

        Bit-identical to `two_phase` on a single device: each shard
        shortlists its rows (dense XLA dot + top_k, or the fused Pallas
        kernel where `sharded._use_fused` says so), rescores its local
        candidates with GLOBAL support indices feeding the noise counters,
        and the candidate sets are all-gathered and merged by (distance,
        global index). See repro/engine/sharded.py for the exactness
        argument.
        """
        from repro.engine import sharded
        return sharded.sharded_two_phase_search(
            q_values, s_values, self.cfg, mesh, axes=axes, k=k, valid=valid,
            backend=self.resolved_backend,
            fused_min_rows=self.fused_min_rows)
