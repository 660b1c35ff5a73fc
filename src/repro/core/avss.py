"""Symmetric (SVSS) and Asymmetric (AVSS) vector similarity search on MCAM.

Storage layout (paper Fig. 4, generalised): a support vector with d dimensions
encoded into L code words per dimension occupies a grid of NAND strings

    (n_seg, L) strings,   n_seg = ceil(d / string_len)

where string (seg, c) holds the c-th code word of the ``string_len`` dimensions
in segment ``seg``. Code-word significance is therefore uniform within a
string, realising Eq. (2)'s weighted accumulation with one weight per string.

* SVSS: the query is encoded identically, and every string requires its own
  word-line cycle  ->  iterations = L * n_seg.
* AVSS: the query keeps ONE 4-level word per dimension; the same word-line
  setting is shared by all L strings of a segment, which are sensed in
  parallel  ->  iterations = n_seg.  (32x fewer for Omniglot's CL=32,
  25x for CUB's CL=25 -- paper Table 2.)

The search result per (query, support) is the accumulated, weighted SA vote
count over all strings; prediction is 1-NN on votes (vote ties broken by the
ideal digital distance) or per-class vote sums.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core import mcam as mcam_lib
from repro.core.encodings import Encoding, make_encoding
from repro.core.mcam import MCAMConfig

Mode = str  # 'svss' | 'avss'


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    """End-to-end VSS configuration."""

    encoding: str = "mtmc"
    cl: int = 8
    mode: Mode = "avss"
    mcam: MCAMConfig = dataclasses.field(default_factory=MCAMConfig)
    noisy: bool = True          # device/read noise on (paper-faithful)
    use_kernel: str = "auto"    # 'ref' | 'pallas' | 'auto'
    query_chunk: int = 8        # reference-path chunking over queries

    @property
    def enc(self) -> Encoding:
        return make_encoding(self.encoding, self.cl)


def n_segments(d: int, string_len: int = mcam_lib.DEFAULT_STRING_LEN) -> int:
    return math.ceil(d / string_len)


def search_iterations(d: int, enc: Encoding, mode: Mode,
                      string_len: int = mcam_lib.DEFAULT_STRING_LEN) -> int:
    """Word-line cycles per query (paper Sec. 3.2)."""
    seg = n_segments(d, string_len)
    return seg if mode == "avss" else seg * enc.length


def strings_per_support(d: int, enc: Encoding,
                        string_len: int = mcam_lib.DEFAULT_STRING_LEN) -> int:
    return n_segments(d, string_len) * enc.length


# ---------------------------------------------------------------------------
# Layout helpers.
# ---------------------------------------------------------------------------


def _segment_dims(x: jax.Array, string_len: int) -> jax.Array:
    """(..., d) -> (..., n_seg, string_len), zero-padded."""
    d = x.shape[-1]
    seg = n_segments(d, string_len)
    pad = seg * string_len - d
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(*x.shape[:-1], seg, string_len)


def layout_support_words(words: jax.Array,
                         string_len: int = mcam_lib.DEFAULT_STRING_LEN
                         ) -> jax.Array:
    """Code words (..., d, L) -> string grid (..., n_seg, L, string_len).

    The layout half of `layout_support`, split out so hardware-aware
    training can feed STE-encoded (float, differentiable) words through the
    SAME placement the engine programs at write time: pure pad/reshape/
    transpose, so gradients flow and the forward is bit-identical."""
    codes = jnp.moveaxis(words, -1, -2)          # (..., L, d)
    codes = _segment_dims(codes, string_len)     # (..., L, seg, sl)
    return jnp.moveaxis(codes, -3, -2)           # (..., seg, L, sl)


def layout_support(values: jax.Array, enc: Encoding,
                   string_len: int = mcam_lib.DEFAULT_STRING_LEN) -> jax.Array:
    """Quantized support values (N, d) -> string grid (N, n_seg, L, string_len).

    Padding dimensions store code 0 and are always searched with query word 0,
    contributing zero mismatch (and rho**0 resistance, as real pass cells do).

    This is WRITE-TIME work: MemoryStore.write materialises the grid once at
    programming time, and serving jits against the stored constant. The
    named_scope tags any traced call in compiled HLO so tests can assert the
    serve decode step does NOT re-lay out the store per step.
    """
    with jax.named_scope("layout_support"):
        return layout_support_words(enc.encode(values), string_len)


def layout_query(values: jax.Array, enc: Encoding, mode: Mode,
                 string_len: int = mcam_lib.DEFAULT_STRING_LEN) -> jax.Array:
    """Quantized query (B, d) -> word-line grid (B, n_seg, L_q, string_len).

    AVSS: L_q == 1 (values already in [0, 4)); SVSS: L_q == enc.length.
    """
    if mode == "avss":
        return _segment_dims(values, string_len)[..., :, None, :]
    return layout_support(values, enc, string_len)


# ---------------------------------------------------------------------------
# Reference search (pure jnp; the Pallas kernels mirror this bit-exactly).
# ---------------------------------------------------------------------------


def _string_ids(n: int, seg: int, L: int) -> jax.Array:
    """(N, seg, L) absolute string ids -- the noise-counter coordinates
    shared by the reference search, the rescore path and the episodic
    training forward (absolute ids are what make noise shard-invariant)."""
    return (jnp.arange(n, dtype=jnp.uint32)[:, None, None] * (seg * L)
            + jnp.arange(seg, dtype=jnp.uint32)[None, :, None] * L
            + jnp.arange(L, dtype=jnp.uint32)[None, None, :])


def votes_from_mismatch(mm: jax.Array, qidx: jax.Array, weights: jax.Array,
                        cfg: SearchConfig, thresholds: jax.Array, *,
                        noisy: bool | None = None,
                        noise_stream: jax.Array | None = None,
                        step_fn=None) -> tuple[jax.Array, jax.Array]:
    """The ONE mismatch-grid -> (votes, dist) forward.

    mm:   (..., N, seg, L, sl) per-cell mismatch levels (float; integer-
          valued in serving, STE-quantized in training).
    qidx: integer query coordinates broadcastable to mm.shape[:-1]
          (a scalar in the per-query reference search, an
          (B, 1, 1, 1) arange in the batched episodic forward) -- the
          absolute coordinates feeding the counter-based noise, so the
          same (query, string) pair draws the same noise everywhere.
    noisy:        overrides cfg.noisy when not None.
    noise_stream: optional extra leading noise coordinate (e.g. a
          training-step-derived stream id). None reproduces the serving
          noise EXACTLY; a stream id redraws fresh noise per step from
          the same counter-based family the hardware model uses.
    step_fn: optional differentiable sense-amp step (mcam.ste_step);
          forward-identical to the hard comparison.

    Serving (`RetrievalEngine.full`, ref backend) and training
    (`RetrievalEngine.episode_votes`) both run THIS function, which is the
    train/serve parity contract: same inputs -> bit-identical votes/dist.
    """
    n, seg, L, sl = mm.shape[-4:]
    if noisy is None:
        noisy = cfg.noisy
    if noisy:
        coords = (qidx, _string_ids(n, seg, L))
        if noise_stream is not None:
            coords = (noise_stream,) + coords
        cur = mcam_lib.string_current(mm, cfg.mcam, noise_idx=coords)
    else:
        cur = mcam_lib.string_current(mm, cfg.mcam)
    votes = mcam_lib.sa_votes(cur, cfg.mcam, thresholds, step_fn=step_fn)
    votes = (votes * weights[None, None, :]).sum((-1, -2))
    dist = (mm.sum(-1) * weights[None, None, :]).sum((-1, -2))
    return votes, dist


def _search_one_query(q_grid: jax.Array, s_grid: jax.Array, qidx: jax.Array,
                      weights: jax.Array, cfg: SearchConfig,
                      thresholds: jax.Array) -> tuple[jax.Array, jax.Array]:
    """q_grid (seg, Lq, sl); s_grid (N, seg, L, sl) -> votes (N,), dist (N,)."""
    mm = jnp.abs(q_grid[None].astype(jnp.int32) - s_grid.astype(jnp.int32))
    mm = mm.astype(jnp.float32)                      # (N, seg, L, sl)
    return votes_from_mismatch(mm, qidx, weights, cfg, thresholds)


def search_quantized(q_values: jax.Array, s_values: jax.Array,
                     cfg: SearchConfig) -> dict[str, jax.Array]:
    """Run the full MCAM search.

    q_values: (B, d) ints -- in [0, 4) for AVSS, [0, enc.levels) for SVSS.
    s_values: (N, d) ints in [0, enc.levels).
    Returns dict with votes (B, N), dist (B, N) (ideal digital distance) and
    iterations (python int).
    """
    # Dispatch lives in the engine layer (repro/engine); this wrapper keeps
    # the historical API for callers that think in terms of raw searches.
    # (The store-centric path is RetrievalEngine.search(MemoryStore...).)
    from repro.engine import RetrievalEngine
    return RetrievalEngine(cfg).full(q_values, s_values)


# ---------------------------------------------------------------------------
# Prediction heads.
# ---------------------------------------------------------------------------


def score_supports(result: dict[str, jax.Array]) -> jax.Array:
    """Votes with infinitesimal ideal-distance tie-breaking. (B, N).

    NOTE: only suitable where a scalar score is needed (class-vote SUMS in
    class_scores / HAT's CE loss). For ranking use best_support: the 1e-6
    epsilon falls below the f32 ulp once votes reach ~16, so argmax over
    this score silently loses the distance tie-break."""
    return result["votes"] - 1e-6 * result["dist"]


def best_support(result: dict[str, jax.Array]) -> jax.Array:
    """Argmax by (votes desc, ideal distance asc, index asc) -- the paper's
    retrieval rule with the vote tie EXACTLY broken by digital distance.
    Works on full (B, N) results and two-phase (B, k) candidate results."""
    votes, dist = result["votes"], result["dist"]
    top = votes.max(axis=-1, keepdims=True)
    return jnp.argmin(jnp.where(votes == top, dist, jnp.inf), axis=-1)


def predict_1nn(result: dict[str, jax.Array], labels: jax.Array) -> jax.Array:
    """Label of the most-similar support (the paper's retrieval rule)."""
    return labels[best_support(result)]


def class_scores(result: dict[str, jax.Array], labels: jax.Array,
                 n_classes: int) -> jax.Array:
    """Per-class vote sums (B, n_classes) with distance tie-breaking."""
    onehot = jax.nn.one_hot(labels, n_classes, dtype=result["votes"].dtype)
    return score_supports(result) @ onehot


def class_mean_votes(votes: jax.Array, labels: jax.Array,
                     n_classes: int) -> jax.Array:
    """Mean vote score per class (B, n_classes) -- HAT's episodic logits
    (paper Sec. 3.3), shared by `meta_loss` and the served evaluation so
    the two heads agree exactly when the underlying votes do."""
    onehot = jax.nn.one_hot(labels, n_classes, dtype=votes.dtype)
    counts = onehot.sum(0) + 1e-8
    return (votes @ onehot) / counts


def predict_class_vote(result, labels, n_classes) -> jax.Array:
    return jnp.argmax(class_scores(result, labels, n_classes), axis=-1)


def accuracy(pred: jax.Array, target: jax.Array) -> jax.Array:
    return (pred == target).mean()
