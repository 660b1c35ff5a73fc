"""Backend registry and resolution for the retrieval engine.

Backends (both produce bit-identical results -- the choice is purely a
performance/hardware decision, see the parity suite in tests/test_engine.py):

  ref     pure-jnp reference (kernels/ref.py semantics); always available.
  pallas  fused Pallas VPU search kernel (kernels/mcam_search.py) for the
          full search. Shortlists take the one phase-1 route every site
          shares (engine/sharded._local_shortlist): XLA's dense dot of the
          query one-hots against the stored projection + lax.top_k.
"""

from __future__ import annotations

import functools

BACKENDS = ("ref", "pallas")


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
