"""Pallas TPU kernel: fused AVSS shortlist (LUT distance matmul + top-k).

The engine's phase-1 shortlist (the two-phase search and the `ideal`
serving mode, unsharded or per shard inside the `shard_map` bodies of
repro/engine/sharded.py) runs this kernel where the dense route's (B, N)
f32 distance block would exceed `sharded.SHORTLIST_DENSE_MAX_BYTES`, on
the 'fused' backend, and from an explicit `fused_min_rows`. Below the
budget XLA's dense dot + exact lax.top_k is the faster route on a TPU v5e
(9.0 ms against this kernel's 101.0 ms at B=64 over 1,048,576 rows), but
it materialises the whole (B, N) distance matrix in HBM before ranking.
This kernel fuses the two: the grid walks the support rows tile by
tile, each step computes the (tile_b, tile_n) distance block on the MXU and
folds it into a running per-query top-k buffer that lives in the
(revisited) output block -- HBM traffic drops from O(B*N) to
O(B*k + N*4d/wpi), where wpi is the packed-word factor below.

Masked rows (never-written slots, ragged-shard label -1 pads) are handled
natively: `valid` enters the kernel as a per-row penalty vector
(0 on valid rows, the integer-exact SHORTLIST_MASK_PENALTY on masked ones)
with its own block stream, so masked rows rank after every valid row while
preserving their relative order -- no extra LUT column, no caller-side
mask plumbing, and shard-local (ragged, non-tile-aligned) row blocks work
unchanged because the wrapper pads any N up to the tile grid.

Tie-breaking contract (bit-identical to jax.lax.top_k on -dist): candidates
are ranked by (distance, support row) lexicographically ascending.
Correctness of the streaming merge (pre-top-k + merge of sorted runs,
which replaced the O(k * (k + tile_n)) per-step extraction loop):

* k is widened internally to kp (the network path pads to a power of two
  >= the 128 lane width, as bitonic stages need it; the native path keeps
  kp = k), and the (tile_b, kp) output block keeps this invariant: after
  grid step j it
  holds the kp lexicographically-smallest (distance, row) pairs of every
  row streamed so far, sorted ascending ((inf, sentinel) pads before kp
  finite candidates exist).
* pre-top-k reduces the incoming (tile_b, tile_n) distance block to its kp
  best, sorted. kp >= k, so no row that can reach the global top-k is ever
  pruned locally (a global top-k row is in its tile's top-k a fortiori).
* the merge of two sorted length-kp runs keeps the kp smallest of their
  union, sorted -- which is exactly the kp best over "rows seen so far",
  restoring the invariant. After the last tile, columns [:k] are the
  global top-k in (distance, row) order, ties included.

Every (distance, row) pair is unique (rows are distinct), so the order is
total and any correct selection yields the same arrays -- which is what
lets the kernel carry two interchangeable implementations of
pre-top-k + merge, selected by `use_network`:

* native (default under interpret mode, i.e. CPU testing): jax.lax.top_k
  for the pre-top-k and a two-key lax.sort for the merge -- single XLA ops.
* network (default when compiling for TPU, where Mosaic lowers neither
  lax.sort nor lax.top_k): a bitonic sorting network built purely from
  roll / compare / where vector ops -- full bitonic sort of the tile,
  then a reverse + pairwise-lexmin + log2(kp)-stage cleanup merge.

Packed LUT operand: the streamed support projection can arrive bit-packed
(kernels/ops.pack_projection, materialised once at MemoryStore.write time)
with wpi = 32/bits words per int32 word, bits in {4, 8, 16, 32} chosen
from the encoding's largest LUT entry. Column m of the packed word holds
projection columns {w*dp + m}, so the kernel unpacks with shift/mask and
accumulates wpi partial dot products over contiguous query slices -- the
sum equals the unpacked dot exactly (integer-valued f32 partials below
2**24), and the streamed operand shrinks up to 8x.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE_B = 8
DEFAULT_TILE_N = 512
LANE = 128         # TPU vector lane width; the internal top-k buffer pads
                   # k up to a power of two >= this (the `k_pad` knob)
_IDX_SENTINEL = 2**30  # pads the buffer before kp finite candidates exist

# Added to the phase-1 distance of masked-out support rows (never-written
# slots, ragged-shard label -1 pad rows). A power of two, so it is exact in
# bf16/f32; > any real LUT distance (3 * d * sum(weights) stays far below
# 2**22 for every paper geometry) and small enough that dist + penalty
# remains integer-exact in f32 (< 2**24). Ordering among masked rows is
# preserved, so backend/sharding bit-parity survives masking. Re-exported
# as repro.kernels.ops.SHORTLIST_MASK_PENALTY (its historical home).
SHORTLIST_MASK_PENALTY = 2.0 ** 22


def _pow2_at_least(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


# ---------------------------------------------------------------------------
# Bitonic network building blocks (the TPU-compilable sort: Mosaic has no
# lax.sort / lax.top_k, so selection is compare-exchange stages of pure
# vector ops). All operate on (tile_b, width) blocks with width a power of
# two; `col` is the broadcasted lane-index iota of the same shape.
# ---------------------------------------------------------------------------


def _lex_lt(ad: jax.Array, ai: jax.Array, bd: jax.Array,
            bi: jax.Array) -> jax.Array:
    """(ad, ai) strictly before (bd, bi) under the (distance, row) order."""
    return (ad < bd) | ((ad == bd) & (ai < bi))


def _exchange(x: jax.Array, col: jax.Array, s: int) -> jax.Array:
    """Value held by each column's stride-s partner (column col XOR s)."""
    fwd = jnp.roll(x, -s, axis=1)
    bwd = jnp.roll(x, s, axis=1)
    return jnp.where((col & s) == 0, fwd, bwd)


def _cmpex(d: jax.Array, i: jax.Array, col: jax.Array, s: int,
           desc: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One compare-exchange stage at stride s: within each partner pair the
    lower column keeps the lex-min (ascending blocks; `desc` flips)."""
    pd = _exchange(d, col, s)
    pi = _exchange(i, col, s)
    upper = (col & s) != 0
    take_min = desc == upper          # truth table: min at the asc-lower /
    use_p = take_min == _lex_lt(pd, pi, d, i)   # desc-upper position
    return jnp.where(use_p, pd, d), jnp.where(use_p, pi, i)


def _bitonic_sort(d: jax.Array, i: jax.Array,
                  col: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Full bitonic sort, ascending in (d, i), over the lane axis."""
    width = d.shape[1]
    size = 2
    while size <= width:
        desc = (col & size) != 0      # block direction of this stage
        s = size // 2
        while s >= 1:
            d, i = _cmpex(d, i, col, s, desc)
            s //= 2
        size *= 2
    return d, i


def _reverse_lanes(x: jax.Array, col: jax.Array) -> jax.Array:
    """Lane reversal via XOR-stride exchanges: flipping every bit of the
    column index (width-1-c == c XOR (width-1)) is the composition of one
    unconditional partner swap per stride, and those commute."""
    s = 1
    while s < x.shape[1]:
        x = _exchange(x, col, s)
        s *= 2
    return x


def _merge_topk(ad: jax.Array, ai: jax.Array, bd: jax.Array, bi: jax.Array,
                col: jax.Array) -> tuple[jax.Array, jax.Array]:
    """kp smallest of two sorted length-kp runs, sorted. [A | reverse(B)]
    is bitonic, so the stride-kp compare-exchange restricted to the lower
    half is the pairwise lex-min of A against reversed B; the result is
    bitonic and dominated by the discarded half, and log2(kp) ascending
    cleanup stages sort it (the tail of a standard bitonic merge)."""
    rd = _reverse_lanes(bd, col)
    ri = _reverse_lanes(bi, col)
    swap = _lex_lt(rd, ri, ad, ai)
    d = jnp.where(swap, rd, ad)
    i = jnp.where(swap, ri, ai)
    asc = (col & 0) != 0              # all-False: ascending cleanup
    s = d.shape[1] // 2
    while s >= 1:
        d, i = _cmpex(d, i, col, s, asc)
        s //= 2
    return d, i


# ---------------------------------------------------------------------------
# The kernel.
# ---------------------------------------------------------------------------


def _dist_block(q: jax.Array, s: jax.Array,
                pack_bits: int | None) -> jax.Array:
    """(tile_b, tile_n) integer-valued f32 distance block on the MXU.

    Unpacked (pack_bits None): one dot against the (tile_n, C) projection
    block. Packed: unpack each of the wpi = 32/pack_bits fields of the
    (tile_n, dp) int32 block and accumulate the partial dot against the
    matching contiguous query slice; the partials are integer-valued f32,
    so the sum is exactly the unpacked dot."""
    dims = (((1,), (1,)), ((), ()))
    if pack_bits is None:
        return jax.lax.dot_general(q, s, dims,
                                   preferred_element_type=jnp.float32)
    wpi = 32 // pack_bits
    dp = s.shape[1]
    if wpi == 1:
        parts = [s.astype(q.dtype)]
    else:
        mask = jnp.int32((1 << pack_bits) - 1)
        parts = [((s >> jnp.int32(pack_bits * w)) & mask).astype(q.dtype)
                 for w in range(wpi)]
    dist: jax.Array | None = None
    for w, part in enumerate(parts):
        d = jax.lax.dot_general(q[:, w * dp:(w + 1) * dp], part, dims,
                                preferred_element_type=jnp.float32)
        dist = d if dist is None else dist + d
    assert dist is not None
    return dist


def _shortlist_kernel(q_ref: Any, s_ref: Any, *refs: Any, kp: int,
                      tile_n: int, n_real: int, masked: bool,
                      use_network: bool, pack_bits: int | None,
                      n_padded: bool, merge: bool) -> None:
    pen_ref, d_ref, i_ref = refs if masked else (None, *refs)
    j = pl.program_id(1)

    if merge:
        @pl.when(j == 0)
        def _init() -> None:
            d_ref[...] = jnp.full_like(d_ref, jnp.inf)
            i_ref[...] = jnp.full_like(i_ref, jnp.int32(_IDX_SENTINEL))

    dist = _dist_block(q_ref[...], s_ref[...], pack_bits)
    if masked:
        dist = dist + pen_ref[...]         # (1, tile_n) row penalty stream
    if use_network or n_padded:
        n_abs = (j * tile_n
                 + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1))
    if n_padded:                           # padded support rows rank last
        dist = jnp.where(n_abs < n_real, dist, jnp.inf)

    if use_network:
        col = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
        td, ti = _bitonic_sort(dist, n_abs, col)
        td, ti = td[:, :kp], ti[:, :kp]    # tile pre-top-k, sorted
    else:
        # interpret-only native path:  # lint: allow=kernel-sort
        neg, pos = jax.lax.top_k(-dist, kp)      # tile pre-top-k, sorted
        td, ti = -neg, j * tile_n + pos
    if not merge:                          # single N step: the tile top-kp
        d_ref[...] = td                    # IS the global top-kp
        i_ref[...] = ti
        return
    if use_network:
        colk = jax.lax.broadcasted_iota(jnp.int32, td.shape, 1)
        d_new, i_new = _merge_topk(d_ref[...], i_ref[...], td, ti, colk)
    else:
        cd = jnp.concatenate([d_ref[...], td], axis=1)
        ci = jnp.concatenate([i_ref[...], ti], axis=1)
        # interpret-only native path:  # lint: allow=kernel-sort
        sd, si = jax.lax.sort((cd, ci), dimension=1, num_keys=2)
        d_new, i_new = sd[:, :kp], si[:, :kp]
    d_ref[...] = d_new
    i_ref[...] = i_new


def lut_shortlist_pallas(q_onehot: jax.Array, s_proj: jax.Array | None,
                         k: int, *,
                         valid: jax.Array | None = None,
                         tile_b: int = DEFAULT_TILE_B,
                         tile_n: int = DEFAULT_TILE_N,
                         k_pad: int = LANE,
                         packed: jax.Array | None = None,
                         pack_bits: int | None = None,
                         interpret: bool | None = None,
                         use_network: bool | None = None
                         ) -> tuple[jax.Array, jax.Array]:
    """(B, 4d) one-hot queries x (N, 4d) LUT projections -> top-k shortlist.

    Returns (dist (B, k) f32, indices (B, k) int32), ranked ascending by
    (distance, support row) -- the exact order jax.lax.top_k(-dist) yields.
    Requires k <= N. N may be any size (ragged shard-local blocks included);
    rows are padded up to the tile grid internally and padded rows can never
    enter the top-k.

    valid: optional (N,) bool row mask. Masked rows get the integer-exact
    SHORTLIST_MASK_PENALTY added to their distance INSIDE the kernel (one
    (1, tile_n) penalty block per grid step), so they rank after every valid
    row, keep their relative (distance, row) order, and surface the penalty
    in their returned dist -- bit-identical to penalising a dense (B, N)
    matrix before lax.top_k.

    tile_b / tile_n / k_pad: tiling knobs (benchmarks/autotune_shortlist.py
    sweeps them). tile_n is rounded to a power of two >= the internal
    buffer width kp (network path: pow2(max(k, k_pad)); native path: k,
    where k_pad is ignored); results are identical for any legal tiling
    (tests/test_engine.py pins this).

    packed / pack_bits: optional bit-packed projection (N, ceil(C/wpi))
    int32 from kernels/ops.pack_projection, streamed INSTEAD of s_proj
    (which may then be None) -- up to 8x less HBM traffic, bit-identical
    distances (see module docstring).

    use_network: force the bitonic-network selection path (the compiled-TPU
    default) or the native lax.top_k/lax.sort path (the interpret default);
    both produce bit-identical results -- the property tests toggle this.

    Example -- supports with constant rows (row r at distance 3*r from the
    all-zeros query) and row 2 masked out:

    >>> import jax, jax.numpy as jnp
    >>> q = jax.nn.one_hot(jnp.zeros((2, 3), jnp.int32), 4).reshape(2, 12)
    >>> s = jnp.tile(jnp.arange(6, dtype=jnp.float32)[:, None], (1, 12))
    >>> valid = jnp.array([True, True, False, True, True, True])
    >>> dist, idx = lut_shortlist_pallas(q, s, 3, valid=valid)
    >>> idx[0].tolist()            # masked row 2 ranks after every valid row
    [0, 1, 3]
    >>> dist[0].tolist()
    [0.0, 3.0, 9.0]
    >>> _, idx_all = lut_shortlist_pallas(q, s, 6, valid=valid)
    >>> idx_all[0].tolist()        # ...but keeps its relative order at the tail
    [0, 1, 3, 4, 5, 2]
    """
    B, K = q_onehot.shape
    if packed is not None:
        assert pack_bits is not None and pack_bits in (4, 8, 16, 32), \
            pack_bits
        N, dp = packed.shape
        wpi = 32 // pack_bits
        width = dp * wpi
        assert width >= K, (width, K)
        # bf16 holds unpacked fields (and the 0/1 one-hot) exactly only up
        # to 8-bit entries; wider fields force the f32 operand path
        if pack_bits > 8 or q_onehot.dtype not in (jnp.bfloat16,
                                                   jnp.float32):
            q_onehot = q_onehot.astype(jnp.float32)
        if width > K:
            q_onehot = jnp.pad(q_onehot, ((0, 0), (0, width - K)))
        s_stream, s_width = packed, dp
    else:
        assert s_proj is not None, "need s_proj when packed is not given"
        N, K2 = s_proj.shape
        assert K == K2, (K, K2)
        pack_bits = None
        if q_onehot.dtype != s_proj.dtype:   # mixed f32 query / bf16 proj is
            dt = jnp.promote_types(q_onehot.dtype, s_proj.dtype)  # exact:
            q_onehot = q_onehot.astype(dt)   # both hold small integers
            s_proj = s_proj.astype(dt)
        s_stream, s_width = s_proj, K
    assert 0 < k <= N, (k, N)

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if use_network is None:
        # Mosaic lowers neither lax.sort nor lax.top_k; the interpreter
        # (plain XLA) runs them natively and far faster than the network
        use_network = not interpret
    if interpret and tile_b == DEFAULT_TILE_B and tile_n == DEFAULT_TILE_N:
        # untuned interpret-mode run (CPU testing / benching): there is no
        # VMEM budget to respect, while every extra grid step costs a pass
        # through the interpreter's block plumbing -- so default to the
        # widest tiles. Explicit tile arguments (the autotune sweep, the
        # tiling-invariance tests) are honoured as given.
        tile_b, tile_n = max(tile_b, min(B, 64)), max(N, tile_n)
    if use_network:
        # the bitonic network needs power-of-two run widths; pad k up to
        # the lane width so compare-exchange stages stay full-lane
        kp = _pow2_at_least(max(k, k_pad, 1))
    else:
        # the native path has no width constraint -- and any kp > k forces
        # a downstream [:, :k] slice of the pallas output, which XLA:CPU
        # fuses into the interpret grid loop catastrophically (~15x)
        kp = max(k, 1)
    tile_b = min(tile_b, B)
    tile_n = max(_pow2_at_least(min(tile_n, max(N, 1))), kp)
    pad_b = (-B) % tile_b
    pad_n = (-N) % tile_n
    if pad_b:
        q_onehot = jnp.pad(q_onehot, ((0, pad_b), (0, 0)))
    if pad_n:
        s_stream = jnp.pad(s_stream, ((0, pad_n), (0, 0)))
    Bp, Np = B + pad_b, N + pad_n
    grid = (Bp // tile_b, Np // tile_n)  # N axis innermost: sequential merge
    args = [q_onehot, s_stream]
    in_specs = [
        pl.BlockSpec((tile_b, q_onehot.shape[1]), lambda i, j: (i, 0)),
        pl.BlockSpec((tile_n, s_width), lambda i, j: (j, 0)),
    ]
    if valid is not None:
        pen = jnp.where(valid, 0.0,
                        SHORTLIST_MASK_PENALTY).astype(jnp.float32)[None, :]
        if pad_n:
            pen = jnp.pad(pen, ((0, 0), (0, pad_n)))
        args.append(pen)
        in_specs.append(pl.BlockSpec((1, tile_n), lambda i, j: (0, j)))
    kernel = functools.partial(_shortlist_kernel, kp=kp, tile_n=tile_n,
                               n_real=N, masked=valid is not None,
                               use_network=use_network, pack_bits=pack_bits,
                               n_padded=pad_n != 0, merge=grid[1] > 1)
    # the scope tags every op of the fused path in compiled HLO metadata, so
    # tests can assert the kernel actually engaged (or stayed out) on a
    # given route -- see tests/test_engine.py
    with jax.named_scope("shortlist_fused"):
        dist, idx = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((tile_b, kp), lambda i, j: (i, 0)),
                pl.BlockSpec((tile_b, kp), lambda i, j: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((Bp, kp), jnp.float32),
                jax.ShapeDtypeStruct((Bp, kp), jnp.int32),
            ],
            interpret=interpret,
        )(*args)
    return dist[:B, :k], idx[:B, :k]
