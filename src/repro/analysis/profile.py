"""Which layer each op of a profiled program belongs to, from the profile.

A profile that `jax.profiler` writes (`<dir>/plugins/profile/<run>/
<host>.xplane.pb`) carries, in its `/host:metadata` plane, the optimized
HLO of every program the process compiled (the `Hlo Proto` stat of one
event-metadata entry per module, named as the device trace's `XLA Modules`
line names the module: `jit_search(<id>)`). Each instruction keeps its
`op_name`, so the layer scopes the engine opens (`hlo_contracts
.LAYER_SCOPES`) map every op the device ran to a layer, the way `op_layers`
does for compiled text:

    >>> table = profile_layers(profile_dir)      # doctest: +SKIP
    >>> table["jit_search(123)"]["shortlist_fused.1"]  # doctest: +SKIP
    'search_shortlist'

Joined with the trace's `XLA Ops` events (each falls inside one module's
execution on the `XLA Modules` line, and is named by its instruction), this
gives device seconds per layer for a profile of any serving process;
`bench/layers.py` does that join for the benchmark's traced window.

`ProfileData` does not expose event metadata, so the file is read here
with a minimal protobuf wire-format reader: no dependency beyond the
standard library.
"""

from __future__ import annotations

import glob
import os
from typing import Iterator

from repro.analysis.hlo_contracts import HloOp, layers_of

METADATA_PLANE = b"/host:metadata"
HLO_PROTO_STAT = b"Hlo Proto"

# field numbers of the messages read (tsl/profiler/protobuf/xplane.proto,
# xla/service/hlo.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_MAP_VALUE = 2
_EVENT_META_NAME, _EVENT_META_STATS = 2, 5
_STAT_META_ID, _STAT_META_NAME = 1, 2
_STAT_META, _STAT_BYTES = 1, 6
_HLO_MODULE = 1
_MODULE_ENTRY, _MODULE_COMPUTATIONS = 2, 3
_COMP_NAME, _COMP_INSTRUCTIONS, _COMP_ID = 1, 2, 5
_INSTR_NAME, _INSTR_OPCODE, _INSTR_METADATA = 1, 2, 7
_INSTR_ID, _INSTR_OPERANDS, _INSTR_CALLS = 35, 36, 38
_METADATA_OP_NAME = 2


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: bytes) -> Iterator[tuple[int, int | bytes]]:
    """(field number, value) of each field of one message: an int for a
    varint, bytes for everything else."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _get(buf: bytes, number: int, default=b""):
    return next((v for f, v in _fields(buf) if f == number), default)


def _ints(values: list) -> list[int]:
    """A repeated int64 field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def hlo_protos(xspace: bytes) -> dict[str, bytes]:
    """{module name: serialized HloProto} from a profile's XSpace bytes."""
    out = {}
    for field, plane in _fields(xspace):
        if field != _SPACE_PLANES or _get(plane, _PLANE_NAME) != \
                METADATA_PLANE:
            continue
        stat_ids = {_get(_get(e, _MAP_VALUE), _STAT_META_ID, 0)
                    for f, e in _fields(plane)
                    if f == _PLANE_STAT_METADATA and _get(
                        _get(e, _MAP_VALUE), _STAT_META_NAME)
                    == HLO_PROTO_STAT}
        for f, entry in _fields(plane):
            if f != _PLANE_EVENT_METADATA:
                continue
            meta = _get(entry, _MAP_VALUE)
            for g, stat in _fields(meta):
                if g == _EVENT_META_STATS and \
                        _get(stat, _STAT_META, 0) in stat_ids:
                    out[_get(meta, _EVENT_META_NAME).decode()] = \
                        _get(stat, _STAT_BYTES)
    return out


def hlo_proto_ops(hlo_proto: bytes) -> tuple[str, list[HloOp]]:
    """(entry computation, every instruction) of a serialized HloProto,
    as `hlo_contracts.parse_hlo` gives them for compiled text."""
    module = _get(hlo_proto, _HLO_MODULE)
    comps = [c for f, c in _fields(module) if f == _MODULE_COMPUTATIONS]
    comp_names = {_get(c, _COMP_ID, 0): _get(c, _COMP_NAME).decode()
                  for c in comps}
    instrs = []
    for c in comps:
        for f, raw in _fields(c):
            if f != _COMP_INSTRUCTIONS:
                continue
            fields: dict[int, list] = {}
            for g, v in _fields(raw):
                fields.setdefault(g, []).append(v)
            instrs.append((comp_names[_get(c, _COMP_ID, 0)], fields))
    names = {_ints(fs.get(_INSTR_ID, [0]))[0]: fs[_INSTR_NAME][0].decode()
             for _, fs in instrs}
    ops = []
    for comp, fs in instrs:
        meta = fs.get(_INSTR_METADATA, [b""])[0]
        ops.append(HloOp(
            fs[_INSTR_NAME][0].decode(), fs[_INSTR_OPCODE][0].decode(),
            _get(meta, _METADATA_OP_NAME).decode(),
            tuple(names[i] for i in _ints(fs.get(_INSTR_OPERANDS, []))),
            comp,
            tuple(comp_names[i] for i in _ints(fs.get(_INSTR_CALLS, [])))))
    return _get(module, _MODULE_ENTRY).decode(), ops


def xplane_path(profile_dir: str) -> str:
    """The one `.xplane.pb` a `jax.profiler` trace wrote under the dir."""
    paths = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {profile_dir}, found "
                           f"{paths}")
    return paths[0]


def profile_layers(profile_dir: str) -> dict[str, dict[str, str | None]]:
    """{module name: {instruction: layer}} for every program a profile
    holds the HLO of (`hlo_contracts.op_layers`' rule; None: an op no
    layer scope covers). Empty when the profile carries no HLO."""
    with open(xplane_path(profile_dir), "rb") as f:
        protos = hlo_protos(f.read())
    return {name: layers_of(*hlo_proto_ops(proto))
            for name, proto in protos.items()}
