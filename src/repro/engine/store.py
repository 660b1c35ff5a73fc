"""MemoryStore: the programmed MCAM memory as an immutable registered pytree.

The paper's premise is that support vectors are *programmed once* into MCAM
strings and searched many times: MTMC encoding happens at write time (Sec.
3.1) and AVSS reads the fixed layout (Sec. 3.2). The store mirrors that --
`write` materialises everything a search ever needs:

  values   (N, d)  int32   quantized support values (ring buffer)
  proj     (N, 4d) bf16    AVSS LUT projection (phase-1 MXU shortlists)
  proj_packed (N, ceil(4d/wpi)) int32  the same projection bit-packed
                           (kernels/ops.pack_projection, wpi = 32/bits LUT
                           words per int32) -- the fused shortlist streams
                           this 4-8x smaller operand instead of `proj`
  s_grid   (N, seg, L, sl) int8  string-grid layout (full search / rescore)
  labels   (N,)    int32   class / token labels; -1 marks an empty slot
                           (never written, or a ragged-shard pad row)
  size     ()      int32   total writes so far (monotonic; ring position)
  lo, hi   ()      f32     calibrated quantization range
  sketch_sums   (S, R, d) int32  phase-0 router sketch: per row-shard,
  sketch_counts (S, R)    int32  per-class-bucket sums/counts of valid
                           rows (engine/router.py). Maintained by both
                           write paths (integer-exact, scatter-free) and
                           rebuilt by `shard`; S=1 on unsharded stores.

so searches -- including the decode loop `serve --retrieval` jits -- run
against write-time constants instead of re-running `layout_support` /
`support_projection` per call. Sharding is a store property:
`shard(mesh, axes)` row-shards the store (padding ragged splits with
label -1 rows that the integer-exact mask penalty ranks last) and records
(mesh, axes) as static metadata, making `RetrievalEngine.search` dispatch
shard-aware with no caller plumbing. Re-sharding always starts from the
LOGICAL `cfg.capacity` rows, so `shard` is idempotent (pads never pad).
`shard` also partitions WITHOUT a mesh (`shard(n_shards=S)`): the store
keeps its global arrays but records S contiguous row blocks in the
router sketch, which is what `SearchRequest.nprobe` routes over; with
`residency="host"` the blocks additionally live in host memory and are
paged onto the device by `engine/pager.ShardPager` (beyond-HBM serving).

Writes on a MULTI-shard store stay shard-LOCAL (the paper's economics:
NAND programming is the cheap in-place operation). `write` dispatches to a
shard_map write-through in which each shard computes which slice of the
(replicated) incoming batch lands in its own ring segment and programs
values/proj/proj_packed/s_grid/labels in place -- the compiled HLO
contains no cross-device collectives and no scatter (tests/test_store.py),
and the result is bit-identical to the unsharded scatter path, including
ragged pads and ring wraparound across shard boundaries. With 1 shard (or
no mesh) the write-through's collective-free advantage cannot exist and
its per-row ring inversion just costs VPU time (7.7x slower in
bench_engine_sharded), so `write` routes single-shard stores through the
plain scatter path -- same bits, fast path.

All update methods are functional (returning a new store); the store is a
pytree, so it passes through jit / shard_map / eval_shape like any array
tree, with (cfg, mesh, axes) as static aux data.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import avss as avss_lib
from repro.core import quantization as quant_lib
from repro.core.avss import SearchConfig
from repro.core.memory import MemoryConfig
from repro.engine import router as router_lib
from repro.kernels import ops as kernel_ops

#: array leaves of the store pytree (register_dataclass data_fields; also
#: the per-leaf set `shard(residency="host")` moves to host memory).
_DATA_FIELDS = ["values", "proj", "proj_packed", "s_grid", "labels",
                "size", "lo", "hi", "sketch_sums", "sketch_counts"]


def _host_device() -> jax.Device:
    """The host (CPU) device for `residency="host"` placement. Raises when
    jax exposes no CPU backend: a host-resident store that silently stayed
    on the device would defeat the point of paging it."""
    return jax.local_devices(backend="cpu")[0]


def _quantize(x: jax.Array, levels: int, lo: jax.Array,
              hi: jax.Array) -> jax.Array:
    # the SAME affine quantizer hardware-aware training fake-quants with
    # (there with an STE round) -- one leg of the train/serve parity
    return quant_lib.affine_quantize(x, levels, lo, hi).astype(jnp.int32)


@partial(jax.tree_util.register_dataclass,
         data_fields=_DATA_FIELDS,
         meta_fields=["cfg", "mesh", "axes", "calibrated", "residency"])
@dataclasses.dataclass(frozen=True)
class MemoryStore:
    """Immutable programmed MCAM store (see module docstring).

    `calibrated` (static metadata) records whether `calibrate` has set the
    quantization range: embeddings quantized against the default (lo=0,
    hi=1) range are garbage words, so `write` refuses ANY input on a
    never-calibrated store (it always quantizes; already-quantized supports
    go through `from_quantized`), and `quantize_queries` refuses float
    queries (integer queries are already words and pass through).

    Lifecycle: create -> calibrate -> write (-> shard), searched through
    `RetrievalEngine.search`:

    >>> import jax.numpy as jnp
    >>> from repro.core.avss import SearchConfig
    >>> from repro.core.memory import MemoryConfig
    >>> from repro.engine import (MemoryStore, RetrievalEngine,
    ...                           SearchRequest)
    >>> cfg = MemoryConfig(capacity=8, dim=4,
    ...                    search=SearchConfig("mtmc", cl=4, mode="avss",
    ...                                        use_kernel="ref"))
    >>> vecs = jnp.linspace(-1.0, 1.0, 12).reshape(3, 4)
    >>> store = MemoryStore.create(cfg).calibrate(vecs)
    >>> store = store.write(vecs, jnp.array([3, 1, 4]))
    >>> int(store.size), store.capacity, int(store.valid.sum())
    (3, 8, 3)
    >>> res = RetrievalEngine(cfg.search).search(
    ...     store, vecs, SearchRequest(mode="two_phase", k=2))
    >>> res.predict().tolist()             # each vector retrieves itself
    [3, 1, 4]
    """

    values: jax.Array
    proj: jax.Array
    proj_packed: jax.Array
    s_grid: jax.Array
    labels: jax.Array
    size: jax.Array
    lo: jax.Array
    hi: jax.Array
    sketch_sums: jax.Array
    sketch_counts: jax.Array
    cfg: MemoryConfig
    mesh: Mesh | None = None
    axes: tuple[str, ...] = ()
    calibrated: bool = False
    residency: str = "device"

    # -- construction --------------------------------------------------------

    @classmethod
    def create(cls, cfg: MemoryConfig) -> "MemoryStore":
        """Empty store: every slot reads as value 0 with label -1, and the
        write-time artifacts (proj, s_grid) are CONSISTENT with value 0 --
        exactly what a later `write` of value 0 would program. This keeps
        empty slots and written slots indistinguishable to phase 1 except
        through the label mask, which is what preserves bit-parity between
        ragged-pad rows, empty slots, and the unsharded search."""
        enc = cfg.search.enc
        zeros = jnp.zeros((cfg.capacity, cfg.dim), jnp.int32)
        # every slot holds the same row, so lay out one and broadcast it:
        # the layouts' lane-padded temporaries scale with the rows laid
        # out (24 GiB for a 1M-row store on a TPU)
        row = zeros[:1]
        fill = lambda a: jnp.broadcast_to(a, (cfg.capacity,) + a.shape[1:])
        proj = kernel_ops.support_projection(row, enc)
        labels = jnp.full((cfg.capacity,), -1, jnp.int32)
        sk_sums, sk_counts = router_lib.build_sketch(zeros, labels, 1)
        return cls(
            values=zeros,
            proj=fill(proj),
            proj_packed=fill(kernel_ops.pack_projection(proj, enc)),
            s_grid=fill(_layout(row, cfg)),
            labels=labels,
            size=jnp.zeros((), jnp.int32),
            lo=jnp.zeros((), jnp.float32),
            hi=jnp.ones((), jnp.float32),
            sketch_sums=sk_sums,
            sketch_counts=sk_counts,
            cfg=cfg,
        )

    @classmethod
    def from_quantized(cls, values: jax.Array, labels: jax.Array,
                       search_cfg: SearchConfig) -> "MemoryStore":
        """Program an already-quantized support set (ints in [0, levels))
        as a full store of capacity == len(values). The episodic evaluation
        path (examples/fsl_omniglot.py) quantizes asymmetrically per
        episode and lands here. Every slot is written, so the layouts are
        built directly (no empty-slot init pass)."""
        n, d = values.shape
        cfg = MemoryConfig(capacity=n, dim=d, search=search_cfg)
        v = values.astype(jnp.int32)
        lab = labels.astype(jnp.int32)
        proj = kernel_ops.support_projection(v, cfg.search.enc)
        sk_sums, sk_counts = router_lib.build_sketch(v, lab, 1)
        return cls(
            values=v,
            proj=proj,
            proj_packed=kernel_ops.pack_projection(proj, cfg.search.enc),
            s_grid=_layout(v, cfg),
            labels=lab,
            size=jnp.asarray(n, jnp.int32),
            lo=jnp.zeros((), jnp.float32),
            hi=jnp.ones((), jnp.float32),
            sketch_sums=sk_sums,
            sketch_counts=sk_counts,
            cfg=cfg,
        )

    @classmethod
    def from_episode(cls, s_emb: jax.Array, q_emb: jax.Array,
                     labels: jax.Array, search_cfg: SearchConfig,
                     clip_std: float = 2.5,
                     capacity: int | None = None) -> "MemoryStore":
        """Program an episode's FLOAT support embeddings the way the
        hardware-aware trainer quantized them: calibrated on the SAME
        support+query sample statistics `quantize_asymmetric` saw. This is
        the one train->write->serve recipe -- searches on the returned
        store are bit-identical to the in-training episodic forward
        (`RetrievalEngine.episode_votes`; tests/test_train_serve_parity.py)
        -- shared by `launch/train.py --hat`, examples/fsl_omniglot.py and
        the parity tests so the calibration convention cannot drift."""
        cfg = MemoryConfig(capacity=capacity or s_emb.shape[0],
                           dim=s_emb.shape[1], search=search_cfg,
                           clip_std=clip_std)
        sample = jnp.concatenate([s_emb.ravel(), q_emb.ravel()])
        return cls.create(cfg).calibrate(sample).write(
            s_emb, labels.astype(jnp.int32))

    @classmethod
    def from_state(cls, state: dict[str, jax.Array],
                   cfg: MemoryConfig) -> "MemoryStore":
        """Adopt a legacy `core.memory` state dict (pre-redesign contract).
        Dicts from old checkpoints may lack the write-time `s_grid`; it is
        derived from `values` (deterministic, so results stay identical)."""
        s_grid = state.get("s_grid")
        if s_grid is None:
            s_grid = _layout(state["values"], cfg)
        packed = state.get("proj_packed")
        if packed is None:
            packed = kernel_ops.pack_projection(state["proj"],
                                                cfg.search.enc)
        # legacy dicts carry no calibration flag; adopt their lo/hi as-is
        # (the pre-redesign API managed calibration itself) so the shims in
        # core/memory.py stay bit-identical. The router sketch is a
        # deterministic integer function of (values, labels), so rebuilding
        # it here (state dicts never carry it) is bit-exact.
        sk_sums, sk_counts = router_lib.build_sketch(
            state["values"], state["labels"], 1)
        return cls(values=state["values"], proj=state["proj"],
                   proj_packed=packed,
                   s_grid=s_grid, labels=state["labels"],
                   size=state["size"], lo=state["lo"], hi=state["hi"],
                   sketch_sums=sk_sums, sketch_counts=sk_counts,
                   cfg=cfg, calibrated=True)

    def to_state(self) -> dict[str, jax.Array]:
        """Legacy state-dict view (the pre-redesign `core.memory` contract,
        plus the write-time `s_grid`)."""
        return {"values": self.values, "proj": self.proj,
                "s_grid": self.s_grid, "labels": self.labels,
                "size": self.size, "lo": self.lo, "hi": self.hi}

    # -- persistence ---------------------------------------------------------

    def save(self, directory: str, step: int = 0) -> None:
        """Persist the programmed store through `repro.checkpoint.ckpt`
        (atomic, sharded, manifest-last): values, labels, the write-time
        proj/s_grid layouts, the calibrated quant range and the ring size
        -- everything a separate serving process needs to `restore` and
        search bit-identically. A sharded store writes its addressable
        shards; restore rebuilds the global arrays (re-`shard` after)."""
        from repro.checkpoint import ckpt
        ckpt.save(directory, step, self._unpad().to_state())

    @classmethod
    def restore(cls, directory: str, cfg: MemoryConfig,
                step: int | None = None) -> "MemoryStore":
        """Load a store previously written by `save`. The result is
        unsharded (call `.shard(mesh, axes)` to place it) and marked
        calibrated -- the persisted (lo, hi) range IS the calibration, so
        searches on the restored store are bit-identical to the writer's
        (round-trip asserted in tests/test_checkpoint.py)."""
        from repro.checkpoint import ckpt
        target = jax.eval_shape(lambda: cls.create(cfg).to_state())
        return cls.from_state(ckpt.restore(directory, target, step=step),
                              cfg)

    # -- derived properties --------------------------------------------------

    @property
    def capacity(self) -> int:
        """Physical rows, including any ragged-shard pad rows."""
        return self.values.shape[0]

    @property
    def dim(self) -> int:
        return self.values.shape[1]

    @property
    def n_shards(self) -> int:
        """Number of row shards: mesh-derived when the store is
        device-sharded, else the router sketch's partition count (logical
        `shard(n_shards=S)` blocks; 1 for an unpartitioned store)."""
        if self.mesh is not None:
            return int(np.prod([self.mesh.shape[a] for a in self.axes]))
        return int(self.sketch_sums.shape[0])

    @property
    def pack_bits(self) -> int:
        """Field width (4/8/16/32) of `proj_packed`, fixed at PACK time by
        the encoding and the stored `proj` dtype. This is the one
        authoritative spelling: consumers must unpack with the width the
        operand was packed with, never re-derive it from a default dtype
        (a bf16-vs-f32 projection changes the width for large-LUT
        encodings -- see ops.projection_pack_bits)."""
        return kernel_ops.projection_pack_bits(self.cfg.search.enc,
                                               self.proj.dtype)

    @property
    def valid(self) -> jax.Array:
        """(N,) bool: slots holding a written support (pad rows and
        never-written slots carry label -1 and are masked out of phase 1
        via the integer-exact SHORTLIST_MASK_PENALTY)."""
        return self.labels >= 0

    # -- programming ---------------------------------------------------------

    def calibrate(self, vectors: jax.Array) -> "MemoryStore":
        """Set the quantization range from a sample of embeddings (std
        clipping clamped to the data extent, paper Sec. 3.3). Must run
        before the first write -- re-calibrating a store that already holds
        programmed rows would silently make their quantized words
        inconsistent with the new range, so that raises."""
        try:
            written = int(self.size) > 0
        except jax.errors.JAXTypeError:
            # under tracing (eval_shape / jit) size has no concrete value,
            # so the guard cannot run -- it protects the eager setup path,
            # which is where calibration happens in practice
            written = False
        if written:
            raise ValueError(
                f"MemoryStore.calibrate: the store already holds "
                f"{int(self.size)} programmed row(s); their quantized words "
                f"were produced under the previous range and would become "
                f"inconsistent with the new one. Calibrate before the first "
                f"write (or build a fresh store and re-program it).")
        # the SAME std-clipped range hardware-aware training computes
        # (quantization.clip_range): calibrating on the sample the trainer
        # quantized over reproduces its range bit-for-bit
        lo, hi = quant_lib.clip_range(vectors, self.cfg.clip_std)
        return dataclasses.replace(self, lo=lo, hi=hi, calibrated=True)

    def write(self, vectors: jax.Array, labels: jax.Array) -> "MemoryStore":
        """Program a batch of float support embeddings (ring buffer).

        Write-time MCAM programming: quantization, the MTMC/AVSS LUT
        projection AND the string-grid layout are all materialised here,
        once, so every later search jits against constants. Batches larger
        than the capacity are rejected (a single batch would overwrite
        itself mid-write).

        On a multi-shard store the write is a shard_map write-through: each
        shard programs the slice of the batch that lands in its own ring
        segment, locally -- no cross-device scatter (streaming-ingest
        path; bit-identical to the unsharded write). A 1-shard (or
        unsharded) store takes the plain scatter path: there is no
        collective to avoid, and the scatter is 7.7x faster there
        (bench_engine_sharded write rows)."""
        n = vectors.shape[0]
        ring = self.cfg.capacity
        assert n <= ring, f"write batch ({n}) exceeds capacity ({ring})"
        if n == 0:
            return self
        if not self.calibrated:
            raise ValueError(
                "MemoryStore.write: writing to a never-calibrated store "
                "would quantize against the default (lo=0, hi=1) range and "
                "program garbage words; call store.calibrate(sample) before "
                "the first write (already-quantized supports go through "
                "MemoryStore.from_quantized instead).")
        v = _quantize(vectors, self.cfg.search.enc.levels, self.lo, self.hi)
        if self.mesh is not None and self.n_shards > 1:
            return self._program_streamed(v, labels, n)
        start = self.size % ring
        idx = (start + jnp.arange(n)) % ring
        return self._program(idx, v, labels, n)

    def _program(self, idx: jax.Array, v: jax.Array, labels: jax.Array,
                 n: int) -> "MemoryStore":
        enc = self.cfg.search.enc
        proj = kernel_ops.support_projection(v, enc)
        lab = labels.astype(jnp.int32)
        new_values = self.values.at[idx].set(v)
        new_labels = self.labels.at[idx].set(lab)
        s, r = self.sketch_sums.shape[0], self.sketch_sums.shape[1]
        if s == 1:
            # incremental sketch: the batch lands on DISTINCT ring slots
            # (n <= capacity), so adding the (new - old) bucket stats over
            # those slots is exact int32 arithmetic -- bit-identical to a
            # full rebuild from (new_values, new_labels)
            ds_new, dc_new = router_lib.bucket_sums(v, lab, r)
            ds_old, dc_old = router_lib.bucket_sums(self.values[idx],
                                                    self.labels[idx], r)
            sk_sums = self.sketch_sums + (ds_new - ds_old)[None]
            sk_counts = self.sketch_counts + (dc_new - dc_old)[None]
        else:
            # logically-partitioned store (mesh=None shard blocks): rows
            # may cross block boundaries, so rebuild -- one one-hot int
            # matmul, still scatter-free
            sk_sums, sk_counts = router_lib.build_sketch(
                new_values, new_labels, s, r)
        return dataclasses.replace(
            self,
            values=new_values,
            proj=self.proj.at[idx].set(proj),
            proj_packed=self.proj_packed.at[idx].set(
                kernel_ops.pack_projection(proj, enc)),
            s_grid=self.s_grid.at[idx].set(_layout(v, self.cfg)),
            labels=new_labels,
            sketch_sums=sk_sums,
            sketch_counts=sk_counts,
            size=self.size + n,
        )

    def _program_streamed(self, v: jax.Array, labels: jax.Array,
                          n: int) -> "MemoryStore":
        """Shard-local write-through: program a quantized batch into a
        row-sharded store with NO cross-device data movement.

        The batch (and its write-time projection/layout, computed once,
        replicated) enters the shard_map unsharded; each shard derives, for
        every row of its own contiguous block, which batch slot (if any)
        the ring assigns to that global row, and selects it in place. The
        ring index math is identical to the scatter path's
        `(start + arange(n)) % capacity`, inverted per row -- so the result
        is bit-identical, including wraparound across shard boundaries --
        and ragged pad rows (global row >= cfg.capacity) are never written.
        Compiled HLO carries no all-gather/all-to-all/scatter collectives
        (asserted in tests/test_store.py)."""
        from repro.engine.sharded import _shard_index

        mesh, axes = self.mesh, self.axes
        ring = self.cfg.capacity
        enc = self.cfg.search.enc
        n_buckets = self.sketch_sums.shape[1]
        start = (self.size % ring).astype(jnp.int32)
        proj_b = kernel_ops.support_projection(v, enc)
        batch = (v, proj_b, kernel_ops.pack_projection(proj_b, enc),
                 _layout(v, self.cfg), labels.astype(jnp.int32))

        def local(start_: jax.Array, v_: jax.Array, proj_: jax.Array,
                  packed_: jax.Array, grid_: jax.Array, labels_: jax.Array,
                  values_loc: jax.Array, proj_loc: jax.Array,
                  packed_loc: jax.Array, grid_loc: jax.Array,
                  labels_loc: jax.Array) -> tuple[jax.Array, ...]:
            rows = values_loc.shape[0]
            g = _shard_index(mesh, axes) * jnp.int32(rows) \
                + jnp.arange(rows, dtype=jnp.int32)       # global row ids
            # batch slot that the ring assigns to global row g (jnp.mod is
            # non-negative for a positive divisor, so pre-start rows wrap)
            j = jnp.mod(g - start_, jnp.int32(ring))
            written = (j < n) & (g < ring)                # pads stay pads
            jc = jnp.minimum(j, jnp.int32(n - 1))         # safe gather idx

            def sel(new: jax.Array, old: jax.Array) -> jax.Array:
                w = written.reshape((-1,) + (1,) * (old.ndim - 1))
                return jnp.where(w, new[jc].astype(old.dtype), old)

            new_vals = sel(v_, values_loc)
            new_labs = sel(labels_, labels_loc)
            # shard-local router sketch rebuild over the POST-write block:
            # one-hot int matmul (router.bucket_sums), so the compiled HLO
            # stays free of scatter AND collectives like the rest of the
            # write-through; exact int32, bit-identical to the scatter
            # path's sketch for the same rows
            sk_sums, sk_counts = router_lib.bucket_sums(new_vals, new_labs,
                                                        n_buckets)
            return (new_vals, sel(proj_, proj_loc),
                    sel(packed_, packed_loc),
                    sel(grid_, grid_loc), new_labs,
                    sk_sums[None], sk_counts[None])

        out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(),) * 6 + (P(axes),) * 5,
            out_specs=(P(axes),) * 7,
            check_vma=False,
        )(start, *batch, self.values, self.proj, self.proj_packed,
          self.s_grid, self.labels)
        return dataclasses.replace(
            self, values=out[0], proj=out[1], proj_packed=out[2],
            s_grid=out[3], labels=out[4],
            sketch_sums=out[5], sketch_counts=out[6], size=self.size + n)

    def quantize_queries(self, queries: jax.Array) -> jax.Array:
        """Float embeddings -> quantized query words ([0, 4) for AVSS,
        [0, levels) for SVSS). Integer queries pass through untouched
        (already quantized, e.g. the episodic evaluation path). Float
        queries on a never-calibrated store raise: quantizing against the
        default (lo=0, hi=1) range returns garbage words."""
        if jnp.issubdtype(queries.dtype, jnp.integer):
            return queries
        if not self.calibrated:
            raise ValueError(
                "MemoryStore.quantize_queries: float queries on a "
                "never-calibrated store (e.g. fresh create() or "
                "from_quantized()) would quantize against the default "
                "(lo=0, hi=1) range and return garbage words; call "
                "store.calibrate(sample) first, or pass pre-quantized "
                "integer queries.")
        cfg = self.cfg.search
        levels = 4 if cfg.mode == "avss" else cfg.enc.levels
        return _quantize(queries, levels, self.lo, self.hi)

    # -- sharding ------------------------------------------------------------

    def shard(self, mesh: Mesh | None = None,
              axes: Sequence[str] = ("data",), *,
              n_shards: int | None = None,
              residency: str = "device") -> "MemoryStore":
        """Row-shard the store and record the partition as a store property
        (RetrievalEngine.search dispatches on it).

        Two placements:

        * `shard(mesh, axes)` -- device-shard over mesh `axes` (today's
          path). The router sketch is rebuilt at the new shard count and
          row-sharded alongside the data.
        * `shard(n_shards=S)` -- LOGICAL partition, no mesh: the store
          keeps its global arrays but the sketch records S contiguous row
          blocks, which `SearchRequest.nprobe` routes over on a single
          device. With `residency="host"` the arrays are additionally
          placed in host (CPU) memory -- such a store is searched through
          `engine/pager.ShardPager`, which pages the visited blocks into
          device HBM (`engine.search` on it raises).

        Ragged splits are supported: when the row count does not divide the
        shard count, the store is padded with label -1 rows programmed to
        value 0 -- indistinguishable from never-written slots, so the mask
        penalty ranks them after every valid row and top-k results stay
        bit-identical to the unsharded search for k <= the unpadded row
        count.

        Idempotent: re-sharding always starts from the LOGICAL
        `cfg.capacity` rows (any ragged pad rows from a previous shard are
        dropped first), so pads never accumulate and
        `shard(mesh_a).shard(mesh_b)` equals `shard(mesh_b)` exactly."""
        if residency not in ("device", "host"):
            raise ValueError(f"unknown residency {residency!r}: expected "
                             f"'device' or 'host'")
        if mesh is not None:
            if residency != "device":
                raise ValueError(
                    "MemoryStore.shard: mesh-sharded stores are device-"
                    "resident; residency='host' applies to logical "
                    "partitions (shard(n_shards=S, residency='host')) "
                    "paged by engine/pager.ShardPager")
            if any(t != AxisType.Auto for t in mesh.axis_types):
                raise ValueError(
                    "MemoryStore.shard: the mesh needs Auto axes (build it "
                    "with repro.launch.mesh.make_mesh); jax.make_mesh "
                    "defaults to Explicit axes, under which the store's "
                    "writes and sketch updates have no unambiguous output "
                    "sharding")
            axes = tuple(axes)
            n_shards = int(np.prod([mesh.shape[a] for a in axes]))
        elif n_shards is None or n_shards < 1:
            raise ValueError("MemoryStore.shard: pass a mesh or "
                             "n_shards >= 1")
        base = self._unpad()
        store = base._pad_rows((-base.capacity) % n_shards)
        sk_sums, sk_counts = router_lib.build_sketch(
            store.values, store.labels, n_shards,
            self.sketch_sums.shape[1])
        store = dataclasses.replace(store, sketch_sums=sk_sums,
                                    sketch_counts=sk_counts)
        if mesh is None:
            if residency == "host":
                dev = _host_device()
                moved = {f: jax.device_put(getattr(store, f), dev)
                         for f in _DATA_FIELDS}
                store = dataclasses.replace(store, **moved)
            return dataclasses.replace(store, mesh=None, axes=(),
                                       residency=residency)
        row = NamedSharding(mesh, P(axes))
        rep = NamedSharding(mesh, P())
        return dataclasses.replace(
            store,
            values=jax.device_put(store.values, row),
            proj=jax.device_put(store.proj, row),
            proj_packed=jax.device_put(store.proj_packed, row),
            s_grid=jax.device_put(store.s_grid, row),
            labels=jax.device_put(store.labels, row),
            size=jax.device_put(store.size, rep),
            lo=jax.device_put(store.lo, rep),
            hi=jax.device_put(store.hi, rep),
            sketch_sums=jax.device_put(store.sketch_sums, row),
            sketch_counts=jax.device_put(store.sketch_counts, row),
            mesh=mesh, axes=axes, residency="device",
        )

    def _unpad(self) -> "MemoryStore":
        """Back to the logical view: drop ragged-shard pad rows and reset
        the router sketch to the unpartitioned S=1 block (so re-`shard`
        always starts from the same logical store, whatever partition came
        before). Does NOT move arrays between memories -- `shard` handles
        placement."""
        n = self.cfg.capacity
        base = self
        if self.capacity != n:
            base = dataclasses.replace(
                self, values=self.values[:n], proj=self.proj[:n],
                proj_packed=self.proj_packed[:n],
                s_grid=self.s_grid[:n], labels=self.labels[:n])
        if base.sketch_sums.shape[0] != 1 or base.residency != "device":
            sk_sums, sk_counts = router_lib.build_sketch(
                base.values, base.labels, 1, base.sketch_sums.shape[1])
            base = dataclasses.replace(base, sketch_sums=sk_sums,
                                       sketch_counts=sk_counts,
                                       residency="device")
        return base

    def _pad_rows(self, pad: int) -> "MemoryStore":
        if pad == 0:
            return self
        enc = self.cfg.search.enc
        zeros = jnp.zeros((pad, self.dim), jnp.int32)
        proj_pad = kernel_ops.support_projection(zeros, enc)
        cat = lambda a, b: jnp.concatenate([a, b], axis=0)
        return dataclasses.replace(
            self,
            values=cat(self.values, zeros),
            proj=cat(self.proj, proj_pad),
            proj_packed=cat(self.proj_packed,
                            kernel_ops.pack_projection(proj_pad, enc)),
            s_grid=cat(self.s_grid, _layout(zeros, self.cfg)),
            labels=cat(self.labels, jnp.full((pad,), -1, jnp.int32)),
        )


def _layout(values: jax.Array, cfg: MemoryConfig) -> jax.Array:
    """Write-time string-grid layout: (n, d) -> (n, seg, L, sl) int8 codes
    (code words are in [0, 3]; int8 is what the kernels consume)."""
    grid = avss_lib.layout_support(values, cfg.search.enc,
                                   cfg.search.mcam.string_len)
    return grid.astype(jnp.int8)
