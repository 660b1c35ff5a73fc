"""Property-based bit-parity: phase 1 in query chunks IS one dense block.

Random sweeps over (B, N, k, chunk) x {masked, tie-heavy} pin the shared
phase-1 block (engine/sharded._local_shortlist) ranked in query chunks --
the route a (B, N) f32 distance block over
`sharded.SHORTLIST_DENSE_MAX_BYTES` takes -- to the unchunked block and
to the dense f32 contract: exact (distance, index) lexicographic order,
SHORTLIST_MASK_PENALTY semantics, tie-heavy stores (support rows drawn
from a small pool so duplicated distances dominate), masked rows inside
the top-k, and a batch no chunk divides.

Skip-clean without hypothesis (it is not in the pinned environment; the
deterministic edge-case twins live in tests/test_engine.py and
tests/test_chunked_shortlist.py).
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis")

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
from hypothesis import HealthCheck, example, given, settings   # noqa: E402
from hypothesis import strategies as st                        # noqa: E402

from repro.core.encodings import make_encoding                 # noqa: E402
from repro.engine import sharded                               # noqa: E402
from repro.kernels import ops as kops                          # noqa: E402

ENC = make_encoding("mtmc", 8)


def _check(n, b, d, k, chunk, masked, ties, seed):
    rng = np.random.default_rng(seed)
    # tie-heavy: rows drawn from a pool of ~n/3 distinct vectors
    pool = rng.integers(0, ENC.levels, (max(1, n // 3) if ties else n, d))
    sv = jnp.asarray(pool[rng.integers(0, pool.shape[0], n)], jnp.int32)
    qv = jnp.asarray(rng.integers(0, 4, (b, d)), jnp.int32)
    valid = jnp.asarray(rng.random(n) > 0.4) if masked else None

    q1h = kops.query_onehot(qv, jnp.float32)
    dense = q1h @ kops.support_projection(sv, ENC, jnp.float32).T
    if valid is not None:
        dense = dense + jnp.where(valid, 0.0,
                                  kops.SHORTLIST_MASK_PENALTY)[None, :]
    neg, idx_ref = jax.lax.top_k(-dense, k)

    proj = kops.support_projection(sv, ENC)              # bf16, as stored
    one = sharded._local_shortlist(q1h, proj, valid, k)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sharded, "SHORTLIST_DENSE_MAX_BYTES", chunk * n * 4)
        assert sharded.shortlist_chunk(b, n) == chunk
        chunked = sharded._local_shortlist(q1h, proj, valid, k)
    for got in (one, chunked):
        np.testing.assert_array_equal(np.asarray(-neg), np.asarray(got[0]))
        np.testing.assert_array_equal(np.asarray(idx_ref),
                                      np.asarray(got[1]))


@settings(max_examples=25, deadline=None,
          suppress_health_check=list(HealthCheck))
@given(n=st.integers(1, 160), b=st.integers(1, 9), d=st.integers(2, 12),
       kfrac=st.floats(0.01, 1.0), cfrac=st.floats(0.0, 1.0),
       masked=st.booleans(), ties=st.booleans(),
       seed=st.integers(0, 2 ** 16))
# one query a chunk, masked rows in the top-k of a tie-heavy store
@example(n=150, b=5, d=6, kfrac=0.875, cfrac=0.0, masked=True, ties=True,
         seed=7)
# a batch no chunk divides (chunks of 3, 3, 1), k == N
@example(n=45, b=7, d=5, kfrac=1.0, cfrac=0.4, masked=True, ties=False,
         seed=11)
def test_fused_equals_dense_property(n, b, d, kfrac, cfrac, masked, ties,
                                     seed):
    k = max(1, min(n, round(kfrac * n)))
    chunk = 1 + int(cfrac * (b - 1))
    _check(n, b, d, k, chunk, masked, ties, seed)
