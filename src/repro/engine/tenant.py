"""TenantStore: N per-tenant MemoryStores stacked into one batched store.

The ROADMAP north star is millions of users, but a `MemoryStore` serves
exactly one support set: a process hosting many tenants would pay one jit
cache entry and one device round-trip per tenant. This module is the
MANN-serving analogue of SEE-MCAM's scalable-array argument: stack every
tenant's programmed store along a leading tenant axis so ONE compiled
search program (`RetrievalEngine.search_tenants`) serves them all.

Stacking rules (enforced by `stack`):

* every store is unsharded and shares one SearchConfig and embedding dim
  (the search program is shared, so its static configuration must be);
* ragged capacities are padded to the stack-wide maximum with the SAME
  label -1 / value-0 rows `MemoryStore.shard` pads ragged splits with --
  consistent write-time layouts, masked by the integer-exact
  SHORTLIST_MASK_PENALTY, so pad rows rank after every valid row and
  bit-parity with the solo per-tenant search survives padding;
* per-tenant state that searches need under jit (values / proj / s_grid /
  labels / size / lo / hi / the router sketch_sums / sketch_counts)
  becomes batched data leaves; per-tenant static metadata (each store's
  MemoryConfig and calibration flag) rides along as aux data, so
  `tenant(i)` round-trips the EXACT original store.

Lifecycle mirrors the solo store: `stack(stores)` -> serve via
`engine.search_tenants` -> `write_at(tenant_id, vectors, labels)` per-
tenant ring writes (functional, shapes preserved, so the compiled search
is never retraced by a write). See docs/architecture.md ("Multi-tenant
serving") and launch/serve.py's `TenantServer` for the coalescing shell.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.memory import MemoryConfig
from repro.engine import router as router_lib
from repro.engine.store import MemoryStore, _layout, _quantize
from repro.kernels import ops as kernel_ops


def tenant_query_rank(tenant_ids: jax.Array) -> jax.Array:
    """(B,) rank of each query within its tenant group, in batch order.

    This is the noise coordinate `search_tenants` feeds the counter-based
    hardware noise: query b gets the batch position it WOULD have in a
    solo per-tenant `engine.search` call over the same tenant's queries
    (in batch order) -- which is exactly what makes the coalesced noisy
    search bit-identical to the per-tenant solo one. O(B^2) one-hot
    cumulation; serving batches are small.

    >>> import jax.numpy as jnp
    >>> tenant_query_rank(jnp.array([2, 0, 2, 2, 0])).tolist()
    [0, 0, 1, 2, 1]
    """
    t = jnp.asarray(tenant_ids)
    same = t[:, None] == t[None, :]                       # (B, B)
    return jnp.tril(same, k=-1).sum(axis=1).astype(jnp.uint32)


@partial(jax.tree_util.register_dataclass,
         data_fields=["values", "proj", "s_grid", "labels", "size", "lo",
                      "hi", "sketch_sums", "sketch_counts"],
         meta_fields=["cfgs", "calibrated"])
@dataclasses.dataclass(frozen=True)
class TenantStore:
    """N per-tenant MemoryStores as ONE batched pytree (module docstring).

    Data leaves carry a leading tenant axis over the solo store's layout:
    values (T, Np, d), proj (T, Np, 4d), s_grid (T, Np, seg, L, sl),
    labels (T, Np), size/lo/hi (T,), sketch_sums (T, 1, R, d) /
    sketch_counts (T, 1, R) (each tenant's unpartitioned router sketch,
    kept write-consistent by `write_at`) -- with Np the stack-wide padded
    capacity. `cfgs` / `calibrated` keep each tenant's ORIGINAL static
    metadata so `tenant(i)` is an exact inverse of `stack`.

    >>> import jax.numpy as jnp
    >>> from repro.core.avss import SearchConfig
    >>> cfg = SearchConfig("mtmc", cl=4, mode="avss", use_kernel="ref")
    >>> a = MemoryStore.from_quantized(          # capacity 3
    ...     jnp.array([[0, 3], [5, 5], [9, 7]]), jnp.array([1, 2, 3]), cfg)
    >>> b = MemoryStore.from_quantized(          # ragged: capacity 1
    ...     jnp.array([[4, 4]]), jnp.array([7]), cfg)
    >>> ts = TenantStore.stack([a, b])
    >>> ts.n_tenants, ts.n_pad, ts.capacities   # padded to max capacity
    (2, 3, (3, 1))
    >>> ts.labels.tolist()                       # label -1 pad rows
    [[1, 2, 3], [7, -1, -1]]
    >>> bool(jnp.array_equal(ts.tenant(1).values, b.values))  # round-trip
    True
    """

    values: jax.Array
    proj: jax.Array
    s_grid: jax.Array
    labels: jax.Array
    size: jax.Array
    lo: jax.Array
    hi: jax.Array
    sketch_sums: jax.Array
    sketch_counts: jax.Array
    cfgs: tuple[MemoryConfig, ...]
    calibrated: tuple[bool, ...]

    # -- construction --------------------------------------------------------

    @classmethod
    def stack(cls, stores: Sequence[MemoryStore]) -> "TenantStore":
        """Stack per-tenant stores along a new leading tenant axis.

        Every store must be unsharded and share one SearchConfig and dim;
        ragged capacities are padded to the maximum with label -1 rows
        exactly like `MemoryStore.shard` pads ragged splits (value-0 rows
        with CONSISTENT write-time layouts, so pads are indistinguishable
        from never-written slots and rank last under the mask penalty).
        """
        if not stores:
            raise ValueError("TenantStore.stack: need at least one store")
        first = stores[0]
        for i, s in enumerate(stores):
            if s.mesh is not None:
                raise ValueError(
                    f"TenantStore.stack: store {i} is sharded; stack "
                    f"unsharded stores (shard-of-stacks is not supported)")
            if s.cfg.search != first.cfg.search or s.dim != first.dim:
                raise ValueError(
                    f"TenantStore.stack: store {i} disagrees with store 0 "
                    f"on SearchConfig/dim; the stacked search program is "
                    f"shared, so its static configuration must be")
        n_pad = max(s.cfg.capacity for s in stores)
        padded = [s._unpad()._pad_rows(n_pad - s.cfg.capacity)
                  for s in stores]
        stk = lambda leaf: jnp.stack([getattr(s, leaf) for s in padded])
        return cls(values=stk("values"), proj=stk("proj"),
                   s_grid=stk("s_grid"),
                   labels=stk("labels"), size=stk("size"), lo=stk("lo"),
                   hi=stk("hi"), sketch_sums=stk("sketch_sums"),
                   sketch_counts=stk("sketch_counts"),
                   cfgs=tuple(s.cfg for s in stores),
                   calibrated=tuple(s.calibrated for s in stores))

    # -- derived properties --------------------------------------------------

    @property
    def n_tenants(self) -> int:
        return self.values.shape[0]

    @property
    def n_pad(self) -> int:
        """Padded per-tenant capacity (the stack-wide maximum)."""
        return self.values.shape[1]

    @property
    def capacities(self) -> tuple[int, ...]:
        """Each tenant's LOGICAL (pre-padding) capacity."""
        return tuple(c.capacity for c in self.cfgs)

    @property
    def cfg(self) -> MemoryConfig:
        """The shared static config of the per-query search views: tenant
        0's MemoryConfig at the padded capacity (all stacked stores agree
        on everything a search reads from it -- `stack` enforces it)."""
        return dataclasses.replace(self.cfgs[0], capacity=self.n_pad)

    # -- solo views ----------------------------------------------------------

    def tenant(self, i: int) -> MemoryStore:
        """Tenant i's solo MemoryStore, exactly as it was stacked: pads
        dropped, original MemoryConfig and calibration flag restored --
        `stack(stores).tenant(i)` equals `stores[i]` leaf-for-leaf."""
        cap = self.cfgs[i].capacity
        return MemoryStore(
            values=self.values[i, :cap], proj=self.proj[i, :cap],
            s_grid=self.s_grid[i, :cap], labels=self.labels[i, :cap],
            size=self.size[i], lo=self.lo[i], hi=self.hi[i],
            sketch_sums=self.sketch_sums[i],
            sketch_counts=self.sketch_counts[i],
            cfg=self.cfgs[i], calibrated=self.calibrated[i])

    def query_view(self, tenant_ids: jax.Array) -> MemoryStore:
        """Per-QUERY store view: every leaf gathered at `tenant_ids`, so
        leaf b is the owning tenant's store row block. The result is a
        MemoryStore pytree with one extra leading batch axis -- exactly
        what `RetrievalEngine.search_tenants` vmaps the single-query
        search over (in_axes=0 on every data leaf, static cfg shared)."""
        take = lambda a: a[tenant_ids]
        return MemoryStore(
            values=take(self.values), proj=take(self.proj),
            s_grid=take(self.s_grid), labels=take(self.labels),
            size=take(self.size), lo=take(self.lo), hi=take(self.hi),
            sketch_sums=take(self.sketch_sums),
            sketch_counts=take(self.sketch_counts),
            cfg=self.cfg, calibrated=True)

    # -- programming ---------------------------------------------------------

    def quantize_queries(self, queries: jax.Array,
                         tenant_ids: jax.Array) -> jax.Array:
        """Float embeddings -> quantized query words, each query against
        the OWNING tenant's calibrated (lo, hi) range -- value-identical
        to `tenant(t).quantize_queries(q)` per query. Integer queries pass
        through untouched. Float queries require EVERY tenant calibrated
        (tenant_ids is traced data, so the guard cannot be per-tenant)."""
        if jnp.issubdtype(queries.dtype, jnp.integer):
            return queries
        if not all(self.calibrated):
            raise ValueError(
                "TenantStore.quantize_queries: float queries on a stack "
                "with never-calibrated tenants "
                f"{[i for i, c in enumerate(self.calibrated) if not c]} "
                "would quantize against the default (lo=0, hi=1) range "
                "and return garbage words; calibrate every store before "
                "stacking, or pass pre-quantized integer queries.")
        cfg = self.cfgs[0].search
        levels = 4 if cfg.mode == "avss" else cfg.enc.levels
        return _quantize(queries, levels, self.lo[tenant_ids][:, None],
                         self.hi[tenant_ids][:, None])

    def write_at(self, tenant_id: int | jax.Array, vectors: jax.Array,
                 labels: jax.Array) -> "TenantStore":
        """Program a batch into ONE tenant's ring (functional update).

        The solo `MemoryStore.write` contract per tenant: quantize against
        the tenant's calibrated range, scatter into its ring at
        `(size % capacity + arange(n)) % capacity` (the LOGICAL capacity,
        so pad rows are never written), materialise proj/s_grid
        write-time. Every leaf keeps its shape, so a compiled
        `search_tenants` program is NEVER retraced by a write --
        `tenant(t)` afterwards equals `stores[t].write(vectors, labels)`
        bit-for-bit. `tenant_id` may be a traced array (one jitted write
        program serves every tenant); the lifecycle guards then need every
        tenant calibrated and `n <= min(capacities)`.
        """
        n = vectors.shape[0]
        if n == 0:
            return self
        caps = self.capacities
        try:
            static_t: int | None = int(tenant_id)
        except (TypeError, jax.errors.JAXTypeError):
            static_t = None                        # traced tenant id
        if static_t is not None:
            if not self.calibrated[static_t]:
                raise ValueError(
                    f"TenantStore.write_at: tenant {static_t} was stacked "
                    f"never-calibrated; calibrate before stacking (already-"
                    f"quantized supports go through "
                    f"MemoryStore.from_quantized).")
            assert n <= caps[static_t], \
                f"write batch ({n}) exceeds tenant capacity " \
                f"({caps[static_t]})"
        else:
            if not all(self.calibrated):
                raise ValueError(
                    "TenantStore.write_at: traced tenant_id on a stack "
                    "with never-calibrated tenants; calibrate every store "
                    "before stacking.")
            assert n <= min(caps), \
                f"write batch ({n}) exceeds the smallest tenant " \
                f"capacity ({min(caps)})"
        t = jnp.asarray(tenant_id, jnp.int32)
        ring = jnp.asarray(caps, jnp.int32)[t]
        enc = self.cfgs[0].search.enc
        v = _quantize(vectors, enc.levels, self.lo[t], self.hi[t])
        idx = (self.size[t] % ring
               + jnp.arange(n, dtype=jnp.int32)) % ring
        proj = kernel_ops.support_projection(v, enc)
        lab = labels.astype(jnp.int32)
        # the tenant's router sketch follows MemoryStore._program's
        # incremental S=1 path exactly (same helper, same int32 delta over
        # the distinct ring slots), so tenant(t) stays bit-identical to
        # the solo store's write
        n_buckets = self.sketch_sums.shape[2]
        ds_new, dc_new = router_lib.bucket_sums(v, lab, n_buckets)
        ds_old, dc_old = router_lib.bucket_sums(self.values[t, idx],
                                                self.labels[t, idx],
                                                n_buckets)
        return dataclasses.replace(
            self,
            values=self.values.at[t, idx].set(v),
            proj=self.proj.at[t, idx].set(proj.astype(self.proj.dtype)),
            s_grid=self.s_grid.at[t, idx].set(_layout(v, self.cfgs[0])),
            labels=self.labels.at[t, idx].set(lab),
            sketch_sums=self.sketch_sums.at[t, 0].add(ds_new - ds_old),
            sketch_counts=self.sketch_counts.at[t, 0].add(dc_new - dc_old),
            size=self.size.at[t].add(n),
        )
