"""ONE spelling of every compiled-HLO invariant the repo guarantees.

The architecture's contract is mostly *negative* statements about compiled
programs: a store-based search must not re-run `layout_support`, a sharded
write must not emit collectives or scatter, the fused shortlist kernel must
engage exactly when the dispatch rule says so. Those statements used to be
string asserts scattered through individual tests, each with its own list
of op spellings. This module is now the single home:

* the registry runner (`repro.analysis.registry`) walks every registered
  (invariant x entry-point x config) cell through the `check_*` functions
  and writes results/contract_report.json;
* the test suite calls the thin `assert_*` wrappers over the SAME
  functions, so a new op spelling (say, a new collective) is added in one
  place and every route inherits the check.

Checkers take the compiled HLO text (`jit(...).lower(...).compile()
.as_text()`) and return the list of offending HLO lines -- empty means the
invariant holds. The scope tags they look for are real compiler metadata:
`layout_support` and `shortlist_fused` are `jax.named_scope` tags that
survive into HLO op metadata (see repro/core/avss.py and
repro/kernels/shortlist.py).
"""

from __future__ import annotations

import re

# Cross-device collectives that must never appear in a shard-local write
# (store._program_streamed) or an unsharded search.
COLLECTIVE_OPS = ("all-gather", "all-reduce", "all-to-all",
                  "collective-permute")

# Every spelling XLA uses for a scatter once it reaches HLO: the op itself
# and the dynamic-update-slice some backends expand it to.
SCATTER_SPELLINGS = ("scatter(", "dynamic-update-slice")

# jax.named_scope tag wrapping the read-time string layout
# (repro/core/avss.layout_support): store-based searches jit against the
# write-time grids and must not contain it.
LAYOUT_SCOPE_TAG = "layout_support"

# jax.named_scope tag wrapping the fused Pallas shortlist
# (repro/kernels/shortlist.lut_shortlist_pallas): present in compiled HLO
# iff the fused kernel was traced.
FUSED_SCOPE_TAG = "shortlist_fused"

# jax.named_scope tag wrapping the phase-0 router's sketch matmul
# (repro/engine/router.route_scores): present in compiled HLO iff a
# search routes through the per-shard summary sketch (nprobe < n_shards).
ROUTER_SCOPE_TAG = "router_sketch"

# Double-precision leak marker: no search/write/training-forward program
# may promote to f64 (jax runs x64-disabled; this guards explicit leaks).
F64_TYPE_TAG = "f64["


def matched_lines(hlo: str, needles) -> list[str]:
    """HLO lines containing any needle (stripped, deduplicated, ordered)."""
    out, seen = [], set()
    for line in hlo.splitlines():
        if any(n in line for n in needles):
            s = line.strip()
            if s not in seen:
                seen.add(s)
                out.append(s)
    return out


_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE_TOKEN = re.compile(r"\w+")


def scoped_lines(hlo: str, tag: str) -> list[str]:
    """HLO lines whose op_name scope path names `tag` as a component
    (`jit(f)/layout_support/mul`, also inside AD wrappers such as
    `jvp(layout_support)`). Matching components, not substrings, keeps
    other metadata -- e.g. the table of Python function names the HLO
    text carries -- from tripping a check."""
    out, seen = [], set()
    for line in hlo.splitlines():
        m = _OP_NAME.search(line)
        if m and tag in _SCOPE_TOKEN.findall(m.group(1)):
            s = line.strip()
            if s not in seen:
                seen.add(s)
                out.append(s)
    return out


# -- checkers: [] == invariant holds ----------------------------------------


def check_no_collectives(hlo: str) -> list[str]:
    """No cross-device collective op appears in the compiled program."""
    return matched_lines(hlo, COLLECTIVE_OPS)


def check_no_scatter_any_spelling(hlo: str) -> list[str]:
    """No scatter under ANY spelling (scatter op or its CPU expansion)."""
    return matched_lines(hlo, SCATTER_SPELLINGS)


def check_scatter_write(hlo: str) -> list[str]:
    """The single-shard / unsharded write DID take the scatter fast path
    (control direction: a scatter, in either spelling, is present --
    XLA:CPU used to expand it to dynamic-update-slice and now keeps it)."""
    if matched_lines(hlo, SCATTER_SPELLINGS):
        return []
    return ["expected a scatter (scatter write path) but the compiled "
            "program contains none"]


def check_no_layout_ops(hlo: str) -> list[str]:
    """Store-based searches jit against write-time grids: the read-time
    `layout_support` scope tag must not appear."""
    return scoped_lines(hlo, LAYOUT_SCOPE_TAG)


def check_layout_ops_present(hlo: str) -> list[str]:
    """Control direction: the raw-array path DOES lay the store out under
    jit, proving the scope tag is visible in this build's HLO text."""
    if scoped_lines(hlo, LAYOUT_SCOPE_TAG):
        return []
    return [f"expected the {LAYOUT_SCOPE_TAG!r} scope tag (read-time "
            f"layout) but the compiled program contains none"]


def check_fused_tag(hlo: str, expected: bool) -> list[str]:
    """The `shortlist_fused` scope tag appears iff the dispatch rule
    (repro/engine/sharded._use_fused) says the fused kernel engages."""
    lines = scoped_lines(hlo, FUSED_SCOPE_TAG)
    if expected and not lines:
        return [f"dispatch rule says the fused shortlist engages but the "
                f"{FUSED_SCOPE_TAG!r} tag is absent from the compiled HLO"]
    if not expected and lines:
        return lines
    return []


def check_router_tag(hlo: str, expected: bool) -> list[str]:
    """The `router_sketch` scope tag appears iff routing is engaged
    (`SearchRequest.nprobe < store.n_shards`) -- exhaustive searches must
    not pay the sketch matmul, routed ones must go through it."""
    lines = scoped_lines(hlo, ROUTER_SCOPE_TAG)
    if expected and not lines:
        return [f"nprobe < n_shards engages the router but the "
                f"{ROUTER_SCOPE_TAG!r} tag is absent from the compiled HLO"]
    if not expected and lines:
        return lines
    return []


def check_no_f64(hlo: str) -> list[str]:
    """No f64 tensor anywhere in the compiled program."""
    return matched_lines(hlo, (F64_TYPE_TAG,))


def check_single_jit_entry_across_tenants(entries) -> list[str]:
    """ONE compiled search program serves any tenant count (PR 9).

    `entries` maps tenant count T -> jit cache entries added by repeated
    `search_tenants` calls at that T (fresh stores / queries / tenant_ids
    each call, same shapes). The multi-tenant contract is exactly one
    entry per T: a second entry at any T means something per-tenant or
    per-write leaked into the trace (e.g. a python-level branch on tenant
    data) and every tenant would pay its own compile again."""
    return [f"tenant count {t}: {n} jit cache entries added "
            f"(expected exactly 1)"
            for t, n in sorted(entries.items()) if n != 1]


# -- assert wrappers (the test-suite surface) -------------------------------


def _raise(violations: list[str], what: str) -> None:
    if violations:
        shown = "\n  ".join(violations[:8])
        raise AssertionError(f"{what}:\n  {shown}")


def assert_no_collectives(hlo: str) -> None:
    _raise(check_no_collectives(hlo), "collective ops in compiled HLO")


def assert_no_scatter_any_spelling(hlo: str) -> None:
    _raise(check_no_scatter_any_spelling(hlo),
           "scatter (any spelling) in compiled HLO")


def assert_scatter_write(hlo: str) -> None:
    _raise(check_scatter_write(hlo), "scatter write path did not engage")


def assert_no_layout_ops(hlo: str) -> None:
    _raise(check_no_layout_ops(hlo),
           "read-time layout_support ops in a store-based search")


def assert_layout_ops_present(hlo: str) -> None:
    _raise(check_layout_ops_present(hlo), "layout scope tag not visible")


def assert_fused_tag(hlo: str, expected: bool) -> None:
    _raise(check_fused_tag(hlo, expected),
           f"fused-shortlist tag mismatch (expected engaged={expected})")


def assert_router_tag(hlo: str, expected: bool) -> None:
    _raise(check_router_tag(hlo, expected),
           f"router-sketch tag mismatch (expected engaged={expected})")


def assert_no_f64(hlo: str) -> None:
    _raise(check_no_f64(hlo), "f64 promotion in compiled HLO")


def assert_single_jit_entry_across_tenants(entries) -> None:
    _raise(check_single_jit_entry_across_tenants(entries),
           "multi-tenant search retraced per tenant count")
