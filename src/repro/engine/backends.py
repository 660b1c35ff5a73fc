"""Backend registry and resolution for the retrieval engine.

Backends (every one produces bit-identical results -- the choice is purely a
performance/hardware decision, see the parity suite in tests/test_engine.py):

  ref     pure-jnp reference (kernels/ref.py semantics); always available.
  pallas  fused Pallas VPU search kernel (kernels/mcam_search.py) for the
          full search; shortlists by the shared dispatch rule
          (engine/sharded._use_fused): XLA's dense dot + lax.top_k, or the
          fused shortlist kernel for a (B, N) block over the budget.
  mxu     alias of `pallas`.
  fused   shortlists always via the fused distance+top-k Pallas kernel
          (kernels/shortlist.py); full search as `pallas`.
"""

from __future__ import annotations

import functools

BACKENDS = ("ref", "pallas", "mxu", "fused")
KERNEL_BACKENDS = ("pallas", "mxu", "fused")


@functools.cache
def kernels_available() -> bool:
    """True once the Pallas kernel package imports. The kernels ship with
    the engine, so an import failure is a broken install: it raises rather
    than quietly serving every search from the `ref` backend."""
    from repro.kernels import ops  # noqa: F401
    return True


def resolve_backend(backend: str = "auto", use_kernel: str = "auto") -> str:
    """Resolve an engine-level override plus a SearchConfig preference.

    `backend` (the engine's own setting) wins over `use_kernel` (the
    SearchConfig field kept for backwards compatibility); "auto" defers.
    """
    for choice in (backend, use_kernel):
        if choice != "auto":
            if choice not in BACKENDS:
                raise ValueError(
                    f"unknown backend {choice!r}; expected one of "
                    f"{BACKENDS + ('auto',)}")
            return choice
    return "pallas"
