"""Behavioural model of the 3D-NAND multi-bit CAM (MCAM) of Tseng et al. [14].

The MCAM stores vectors on NAND strings of ``string_len`` (default 24) unit
cells; a search applies the query on shared word lines and the per-string
current encodes similarity. Physics captured here (paper Fig. 2):

* Each unit cell produces a mismatch level m in {0, 1, 2, 3} between the
  searched word and the stored word.
* The string is a SERIES connection, so we model each cell as a resistance
  growing exponentially with its mismatch level, R(m) = rho**m, and the
  string current as I = string_len / sum_c rho**(m_c). This reproduces both
  measured behaviours in Fig. 2(b)/(c):
    - current decreases monotonically with the summed mismatch, and
    - for a fixed summed mismatch, a single high-mismatch cell dominates
      (the "bottleneck effect": mismatch-3 strings sink far below
      mismatch-1 strings of equal total mismatch).
* Device variation perturbs the effective mismatch exponent with Gaussian
  noise (sigma_device), and the sense path adds multiplicative read noise
  (sigma_read) -- the Gaussian noise model the paper adopts from CAMASim [15].
* A sense amplifier compares the string current against ``n_thresholds``
  reference levels; the per-string vote is the count of thresholds exceeded.

Noise is generated with a counter-based hash (deterministic given a seed and
the absolute (query, string, cell) coordinates) so that the Pallas kernels and
the pure-jnp reference produce bit-identical results, and searches are
reproducible across shardings.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.encodings import MAX_MISMATCH

DEFAULT_STRING_LEN = 24


@dataclasses.dataclass(frozen=True)
class MCAMConfig:
    """Hardware parameters of the simulated MCAM block."""

    string_len: int = DEFAULT_STRING_LEN
    rho: float = 8.0            # per-mismatch-level series resistance ratio
    sigma_device: float = 0.12  # stddev of per-cell mismatch-exponent noise
    sigma_read: float = 0.04    # stddev of multiplicative current read noise
    n_thresholds: int = 8       # SA reference levels
    max_strings: int = 131072   # 128K strings per block [14]
    seed: int = 0

    def thresholds(self) -> np.ndarray:
        """SA reference currents. Calibrated to ideal currents of strings with
        s uniformly-spread single-level mismatches, s geometrically spaced --
        dense resolution near perfect matches where decisions happen."""
        smax = 1.5 * self.string_len
        s = np.unique(np.round(np.geomspace(1.0, smax, self.n_thresholds)))
        while len(s) < self.n_thresholds:  # pad with linear extras
            extra = s[-1:] + np.arange(1, 1 + self.n_thresholds - len(s))
            s = np.unique(np.concatenate([s, extra]))
        s = s[: self.n_thresholds].astype(np.float64)
        i_ideal = self.string_len / ((self.string_len - s) + s * self.rho)
        return np.sort(i_ideal).astype(np.float32)  # ascending


# ---------------------------------------------------------------------------
# Counter-based deterministic noise (shared by kernels and reference).
# ---------------------------------------------------------------------------

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_GOLDEN = 0x9E3779B9
#: seed offset of the second uniform stream of `hash_normal` (Box-Muller)
NORMAL_PAIR_OFFSET = 0x5BD1


def _mix(x: jax.Array) -> jax.Array:
    """murmur3-style 32-bit finalizer (vectorised, uint32 in/out)."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def hash_init(seed: int) -> np.uint32:
    """Initial state of the counter hash for one noise stream."""
    return np.uint32((seed * _GOLDEN + 0x85EBCA6B) & 0xFFFFFFFF)


def hash_fold(h: jax.Array, i: jax.Array, k: int) -> jax.Array:
    """Fold the k-th integer coordinate `i` into hash state `h`.

    Split out of `hash_uniform` so a kernel can fold the coordinates it
    holds fixed (query, string) once and the innermost one (cell) per
    cell -- integer-exact, so the result equals the one-shot hash."""
    step = np.uint32(((k + 1) * _GOLDEN) & 0xFFFFFFFF)
    return _mix((jnp.asarray(i).astype(jnp.uint32) + step) ^ h)


def hash_to_uniform(h: jax.Array) -> jax.Array:
    """uint32 hash state -> uniform in (0, 1), exactly representable.

    The top 23 bits go through int32 (a same-width bitcast, then an
    int32 -> f32 conversion): both lower in XLA and in Mosaic, which has
    no uint32 -> f32 conversion, and (j + 0.5) * 2**-23 is exact in f32,
    so u never rounds to 0 or 1."""
    j = jax.lax.bitcast_convert_type(h >> 9, jnp.int32)
    return (j.astype(jnp.float32) + 0.5) * jnp.float32(2.0 ** -23)


def box_muller(u1: jax.Array, u2: jax.Array) -> jax.Array:
    """Standard normal from two independent uniforms in (0, 1)."""
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(2.0 * jnp.float32(np.pi) * u2)


def hash_uniform(*idx: jax.Array, seed: int) -> jax.Array:
    """Deterministic uniform(0,1) from integer coordinates (broadcasting)."""
    h = jnp.asarray(hash_init(seed))
    for k, i in enumerate(idx):
        h = hash_fold(h, i, k)
    return hash_to_uniform(h)


def hash_normal(*idx: jax.Array, seed: int) -> jax.Array:
    """Deterministic standard normal via Box-Muller over two hash streams."""
    return box_muller(hash_uniform(*idx, seed=seed),
                      hash_uniform(*idx, seed=seed + NORMAL_PAIR_OFFSET))


# ---------------------------------------------------------------------------
# String current + sense amplifier.
# ---------------------------------------------------------------------------


def noisy_mismatch(m: jax.Array, device_noise: jax.Array,
                   cfg: MCAMConfig) -> jax.Array:
    """Effective mismatch exponent of a cell under device variation."""
    return jnp.clip(m + cfg.sigma_device * device_noise, 0.0,
                    float(MAX_MISMATCH))


def cell_resistance(m: jax.Array, cfg: MCAMConfig) -> jax.Array:
    """Series resistance rho**m of cells at (effective) mismatch m."""
    return jnp.exp(m * jnp.float32(np.log(cfg.rho)))


def string_resistance(cell_mismatch: jax.Array, cfg: MCAMConfig,
                      device_noise: jax.Array | None = None) -> jax.Array:
    """Sum of per-cell series resistances; reduces the trailing axis.

    cell_mismatch: (..., cells) float or int mismatch levels in [0, 3].
    device_noise:  optional (..., cells) standard-normal perturbations.

    The sum is a left fold in cell order, written out: a float reduction
    is free to reorder, and the search kernel (kernels/mcam_search.py)
    accumulates its cells in exactly this order, so the two agree bit for
    bit on every compiler.
    """
    m = cell_mismatch.astype(jnp.float32)
    if device_noise is not None:
        m = noisy_mismatch(m, device_noise, cfg)
    r = cell_resistance(m, cfg)
    total = r[..., 0]
    for c in range(1, r.shape[-1]):
        total = total + r[..., c]
    return total


def current_from_resistance(r_sum: jax.Array, n_cells: int, cfg: MCAMConfig,
                            read_noise: jax.Array | None = None) -> jax.Array:
    """I = n_cells / sum_R, normalised so a perfect match reads 1.0."""
    i = jnp.float32(n_cells) / r_sum
    if read_noise is not None:
        i = i * (1.0 + cfg.sigma_read * read_noise)
    return i


def string_current(cell_mismatch: jax.Array, cfg: MCAMConfig, *,
                   noise_idx: tuple[jax.Array, ...] | None = None) -> jax.Array:
    """Full noisy current for strings of cells; reduces the trailing axis.

    noise_idx: integer coordinate arrays broadcastable to
      cell_mismatch.shape[:-1]; when given, deterministic device/read noise is
      derived from them (plus the cell index for device noise).
    """
    n_cells = cell_mismatch.shape[-1]
    if noise_idx is None:
        r = string_resistance(cell_mismatch, cfg)
        return current_from_resistance(r, n_cells, cfg)
    cell = jnp.arange(n_cells, dtype=jnp.uint32)
    bidx = tuple(jnp.asarray(i)[..., None] for i in noise_idx)
    dn = hash_normal(*bidx, cell, seed=cfg.seed)
    rn = hash_normal(*noise_idx, seed=cfg.seed + 0x2C1B)
    r = string_resistance(cell_mismatch, cfg, device_noise=dn)
    return current_from_resistance(r, n_cells, cfg, read_noise=rn)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def ste_step(x: jax.Array, tau: float) -> jax.Array:
    """Sense-amp comparator STE: hard step forward, sigmoid gradient
    backward (paper Fig. 8(c)). The forward is EXACTLY the comparison the
    serving `sa_votes` makes -- (x > 0) == (current > threshold) -- so
    training through it and serving without it agree bit-for-bit."""
    return (x > 0).astype(jnp.float32)


def _ste_step_fwd(x, tau):
    return (x > 0).astype(jnp.float32), x


def _ste_step_bwd(tau, x, g):
    s = jax.nn.sigmoid(x / tau)
    return (g * s * (1 - s) / tau,)


ste_step.defvjp(_ste_step_fwd, _ste_step_bwd)


def sa_votes(currents: jax.Array, cfg: MCAMConfig,
             thresholds: jax.Array | None = None, *,
             step_fn=None) -> jax.Array:
    """Sense-amplifier voting: count of reference levels the current exceeds.

    step_fn: optional differentiable step (e.g. `partial(ste_step, tau=...)`
    via a lambda) used by hardware-aware training; its forward must equal
    the hard comparison, which `ste_step` guarantees -- the vote VALUES are
    identical either way, only gradients differ."""
    th = jnp.asarray(cfg.thresholds() if thresholds is None else thresholds)
    if step_fn is None:
        return (currents[..., None] > th).sum(-1).astype(jnp.float32)
    return step_fn(currents[..., None] - th).sum(-1)


def ideal_current(total_mismatch: jax.Array, cfg: MCAMConfig) -> jax.Array:
    """Noise-free current of a string whose mismatch is spread one level per
    cell (the best case for a given total) -- used for SA calibration."""
    s = total_mismatch.astype(jnp.float32)
    n = jnp.float32(cfg.string_len)
    return n / ((n - s) + s * cfg.rho)
