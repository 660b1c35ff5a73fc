"""ONE spelling of every compiled-HLO invariant the repo guarantees.

The architecture's contract is mostly *negative* statements about compiled
programs: a store-based search must not re-run `layout_support`, a sharded
write must not emit collectives or scatter, the phase-0 router must engage
exactly when a request routes. Those statements used to be
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
`layout_support` and `router_sketch` are `jax.named_scope` tags that
survive into HLO op metadata (see repro/core/avss.py and
repro/engine/router.py).
"""

from __future__ import annotations

import dataclasses
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

# jax.named_scope tag wrapping the phase-0 router's sketch matmul
# (repro/engine/router.route_scores): present in compiled HLO iff a
# search routes through the per-shard summary sketch (nprobe < n_shards).
ROUTER_SCOPE_TAG = "router_sketch"

# The layers of a search and of a store write, one jax.named_scope each,
# in the order a request passes through them. Every op the engine traces
# sits under one of them (repro/engine/), so a compiled program's ops map to
# layers (`op_layers`) and a profile's device time splits by layer
# (repro/analysis/profile.py). The tags above nest inside them.
QUANTIZE_SCOPE = "search_quantize"     # MemoryStore.quantize_queries
ROUTE_SCOPE = "search_route"           # phase 0 (router_sketch inside)
SHORTLIST_SCOPE = "search_shortlist"   # phase 1, whatever implements it
RESCORE_SCOPE = "search_rescore"       # phase 2 and `full` mode's search
MERGE_SCOPE = "search_merge"           # sharded all-gather + top-k merge
ANSWER_SCOPE = "search_answer"         # label gather, vote mask, predict
WRITE_SCOPE = "store_write"            # MemoryStore.write
CALIBRATE_SCOPE = "store_calibrate"    # MemoryStore.calibrate
# The kNN-LM decode step (launch/steps.make_serve_step_with_mcam) and the
# serving prefill: the decoder (embedding, every layer, final norm, LM head
# and its log-softmax), the kNN head around the store search (query, vote
# softmax, mixture, greedy token; the search keeps its own scopes inside),
# and the prompt forward that fills the decode cache.
DECODER_SCOPE = "decoder"              # models/transformer.decode_step
KNN_SCOPE = "knn_head"                 # the kNN-LM head of the serve step
PREFILL_SCOPE = "prefill"              # models/transformer.prefill
LAYER_SCOPES = (QUANTIZE_SCOPE, ROUTE_SCOPE, SHORTLIST_SCOPE, RESCORE_SCOPE,
                MERGE_SCOPE, ANSWER_SCOPE, WRITE_SCOPE, CALIBRATE_SCOPE,
                DECODER_SCOPE, KNN_SCOPE, PREFILL_SCOPE)

# Opcodes that move or name data without computing it. One that carries no
# layer of its own (a copy or bitcast that layout assignment inserted, an
# async copy's start/done pair, a parameter) takes the layer of its user.
PLUMBING_OPCODES = frozenset({
    "parameter", "constant", "copy", "copy-start", "copy-done", "bitcast",
    "tuple", "get-tuple-element"})

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


# -- op -> layer map ----------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HloOp:
    """One instruction of a compiled program, as far as its layer needs."""
    name: str
    opcode: str
    op_name: str                 # "" where the compiler made the op up
    operands: tuple[str, ...]
    computation: str
    calls: tuple[str, ...]       # the computations it calls


# a computation's header starts in the first column and opens a block; its
# instructions are indented `[ROOT ]%name = <shape> opcode(...), attrs`
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_REFERENCE = re.compile(r"%([\w.\-]+)")
# ops of these run where the trace can see them: the entry computation and
# the bodies of control flow (fused and reducer computations do not)
_CONTROL_FLOW = frozenset({"while", "conditional", "call"})


def parse_hlo(hlo: str) -> tuple[str, list[HloOp]]:
    """(entry computation, every instruction) of compiled HLO text."""
    blocks: list[tuple[str, list[tuple[str, str]]]] = []
    entry = ""
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            blocks.append((m.group(2), []))
            entry = m.group(2) if m.group(1) else entry
            continue
        m = _INSTRUCTION.match(line)
        if m and blocks:
            blocks[-1][1].append((m.group(1), m.group(2)))
    computations = {name for name, _ in blocks}
    ops = []
    for comp, body in blocks:
        names = {n for n, _ in body}
        for name, rhs in body:
            opcode = _OPCODE.search(rhs)
            op_name = _OP_NAME.search(rhs)
            refs = _REFERENCE.findall(rhs)
            ops.append(HloOp(
                name, opcode.group(1) if opcode else "",
                op_name.group(1) if op_name else "",
                tuple(r for r in refs if r in names), comp,
                tuple(r for r in refs if r in computations)))
    return entry, ops


def innermost_layer(op_name: str) -> str | None:
    """The innermost `LAYER_SCOPES` component of an op_name scope path."""
    found = [t for t in _SCOPE_TOKEN.findall(op_name) if t in LAYER_SCOPES]
    return found[-1] if found else None


def layers_of(entry: str, ops: list[HloOp]) -> dict[str, str | None]:
    """{instruction: layer} over the ops a trace can show (`op_layers`)."""
    visible, todo = set(), [entry]
    calls = {}
    for o in ops:
        if o.opcode in _CONTROL_FLOW:
            calls.setdefault(o.computation, []).extend(o.calls)
    while todo:
        comp = todo.pop()
        if comp not in visible:
            visible.add(comp)
            todo.extend(calls.get(comp, ()))
    ops = [o for o in ops if o.computation in visible]
    layer = {o.name: innermost_layer(o.op_name) for o in ops}
    users: dict[str, list[str]] = {}
    for o in ops:
        for a in o.operands:
            users.setdefault(a, []).append(o.name)
    pending = [o for o in ops if layer[o.name] is None
               and (not o.op_name or o.opcode in PLUMBING_OPCODES)]
    # users first, to a fixed point (a copy -> bitcast -> fusion chain takes
    # the fusion's layer); then operands for what no user names (a root
    # tuple, a copy of a result)
    for near in (lambda o: users.get(o.name, []),
                 lambda o: users.get(o.name, []) + list(o.operands)):
        while pending:
            for o in pending:
                layer[o.name] = next((layer[n] for n in near(o)
                                      if layer.get(n)), None)
            left = [o for o in pending if layer[o.name] is None]
            if len(left) == len(pending):
                break
            pending = left
    return layer


def op_layers(hlo: str) -> dict[str, str | None]:
    """{instruction: layer} of a compiled program's text, for every op that
    can appear in a device trace: the innermost `LAYER_SCOPES` component of
    its op_name; a compiler-made op (no op_name) or a plumbing op
    (`PLUMBING_OPCODES`) without one takes its user's layer, failing that
    its operand's. None: an op of the program's own that no layer scope
    covers."""
    return layers_of(*parse_hlo(hlo))


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


def check_phase1_one_block(hlo: str) -> list[str]:
    """Phase 1 ranks every query in one block: no `while` loop in the
    `search_shortlist` layer. A search whose (B, N) f32 distance block
    fits `engine.sharded.SHORTLIST_DENSE_MAX_BYTES` must compile to the
    unchunked dot + top-k; query chunks (a loop) are for blocks over it."""
    _, ops = parse_hlo(hlo)
    return [f"{o.name}: a while loop in {SHORTLIST_SCOPE} ({o.op_name})"
            for o in ops if o.opcode == "while"
            and innermost_layer(o.op_name) == SHORTLIST_SCOPE]


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


def assert_router_tag(hlo: str, expected: bool) -> None:
    _raise(check_router_tag(hlo, expected),
           f"router-sketch tag mismatch (expected engaged={expected})")


def assert_phase1_one_block(hlo: str) -> None:
    _raise(check_phase1_one_block(hlo), "phase 1 ranks in query chunks")


def assert_no_f64(hlo: str) -> None:
    _raise(check_no_f64(hlo), "f64 promotion in compiled HLO")


def assert_single_jit_entry_across_tenants(entries) -> None:
    _raise(check_single_jit_entry_across_tenants(entries),
           "multi-tenant search retraced per tenant count")
