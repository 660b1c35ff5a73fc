"""Static-analysis contract guard: HLO contracts, AST lint, resources.

Three passes, one CLI (`python -m repro.analysis`):

  run        compile every registered (invariant x entry-point x config)
             cell and check the compiled HLO (repro/analysis/registry.py,
             hlo_contracts.py); writes results/contract_report.json.
  lint       repo-specific AST rules over src/ (repro/analysis/lint.py).
  diff       compare two contract reports; new failures exit non-zero.
  cost       the resource oracle (repro/analysis/cost.py): one static
             {flops, hbm_bytes_read/written, temp_bytes, peak_bytes,
             jit_entries} row per registry route, derived from
             cost_analysis()/memory_analysis() + an HLO op census;
             writes results/resource_report.json.
  cost-diff  compare two resource reports against a relative tolerance;
             drift or a lost route exits non-zero (CI gates pushes
             against the committed RESOURCES_baseline.json).

The test suite asserts its HLO expectations through the same
`hlo_contracts.assert_*` helpers the registry checks with, so every
invariant has exactly ONE spelling -- and every
cost_analysis()/memory_analysis() read goes through cost.py (the
`cost-call` lint rule enforces it).
"""

from repro.analysis import hlo_contracts  # noqa: F401
