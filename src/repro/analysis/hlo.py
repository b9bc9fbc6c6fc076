"""Shared optimized-HLO parsing for the analyzers in this package.

One home for the HLO text machinery that ``collectives`` and ``traffic``
build their structural proofs on: the dtype/byte tables, the
op/computation regexes, :func:`parse_module`, trip counts of while loops,
and the collective bookkeeping.

``compiled.cost_analysis()`` counts a while-loop body ONCE, regardless of
trip count, so the analyzers walk the parsed computation graph instead and
multiply each loop body by :func:`trip_count`.

Everything here is pure-python text processing: no jax import.
"""
from __future__ import annotations

import dataclasses
import re

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")

_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[^\s=]+)\s*=\s*"
    r"(?P<type>\([^()]*\)|[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<op>[\w\-]+)\((?P<args>.*?)\)(?P<rest>.*)$")

_COMP_RE = re.compile(r"^(ENTRY\s+)?%?(?P<name>[\w\.\-]+)\s*\(.*\)\s*->.*{\s*$")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
# bytes-on-the-wire multiplier per unit buffer (ring algorithms): a ring
# all-reduce moves ~2x its buffer, all-gather/reduce-scatter (n-1)/n ~ 1x
COLL_WIRE = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
             "all-to-all": 1.0, "collective-permute": 1.0}

#: slice-family ops whose RESULT (or update operand) keeps its exact shape
#: through fusion — the reliable, physically-meaningful byte measurements
#: in an optimized module (see ``analysis.traffic``)
SLICE_OPS = ("dynamic-slice", "gather", "slice")


def base_kind(kind: str) -> str:
    """Collective/async op base name: ``all-gather-start`` -> ``all-gather``."""
    return kind[:-6] if kind.endswith("-start") else kind


def shape_info(type_str: str) -> tuple[int, int]:
    """(elements, bytes) of a type string (sums tuple components)."""
    elems = byts = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        byts += n * _DTYPE_BYTES[dt]
    return elems, byts


@dataclasses.dataclass
class Op:
    name: str
    kind: str
    type_str: str
    args: list
    rest: str
    bytes: int


@dataclasses.dataclass
class Computation:
    name: str
    ops: list
    by_name: dict


def parse_module(text: str) -> dict:
    comps: dict = {}
    cur = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP_RE.match(line)
            if m:
                cur = Computation(m.group("name"), [], {})
            continue
        if line.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        _, byts = shape_info(m.group("type"))
        args = [a.strip().lstrip("%") for a in
                _split_args(m.group("args"))]
        op = Op(m.group("name"), m.group("op"), m.group("type"), args,
                m.group("rest"), byts)
        cur.ops.append(op)
        cur.by_name[op.name] = op
    return comps


def entry_name(text: str, comps: dict) -> str:
    """Name of the module's ENTRY computation (last one in the text)."""
    m = re.search(r"^ENTRY\s+%?([\w\.\-]+)", text, re.M)
    return m.group(1) if m else next(iter(comps))


def _split_args(s: str) -> list:
    """Split top-level comma-separated operand names."""
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur))
    return [a for a in (x.strip() for x in out) if a]


_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALL_RE = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)="
                      r"(\{[^}]*\}|%?[\w\.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def called_comps(rest: str) -> list:
    """Computation names called by an op (fusion/call/while/conditional)."""
    out = []
    for m in _CALL_RE.finditer(rest):
        v = m.group(1)
        if v.startswith("{"):
            out.extend(x.strip().lstrip("%") for x in
                       v.strip("{}").split(",") if x.strip())
        else:
            out.append(v.lstrip("%"))
    return out


def trip_count(op: Op, comps: dict) -> int:
    """Executed iterations of a while op: the ``known_trip_count``
    annotation when XLA proved one, else the largest s32 constant in the
    condition computation (the bound of a counted loop); dynamic whiles
    degrade to 1."""
    m = _TRIP_RE.search(op.rest)
    if m:
        return int(m.group(1))
    best = 1
    for cname in called_comps(op.rest):
        comp = comps.get(cname)
        if comp is None:
            continue
        for o in comp.ops:
            if o.kind == "constant" and o.type_str.startswith("s32") \
                    and o.args and o.args[0].isdigit():
                best = max(best, int(o.args[0]))
    return best


def replica_group_size(op: Op) -> int | None:
    """Participant count of a collective's first replica group, if the op
    carries literal ``replica_groups={{...}}`` (None for the iota-tile
    encodings some versions emit)."""
    m = _GROUPS_RE.search(op.rest)
    if m is None:
        return None
    return len([x for x in m.group(1).split(",") if x])


def operand_bytes(op: Op, comp: Computation) -> int:
    total = 0
    for a in op.args:
        # strip inline type prefix if present ("f32[..] %x") and constants
        name = a.split()[-1].lstrip("%")
        ref = comp.by_name.get(name)
        if ref is not None:
            total += ref.bytes
    return total


def _arg_op(op: Op, comp: Computation, i: int):
    if i >= len(op.args):
        return None
    return comp.by_name.get(op.args[i].split()[-1].lstrip("%"))


@dataclasses.dataclass
class CollectiveStats:
    """Module-wide collective census (counts are STATIC op counts — no loop
    trip multiplication)."""
    bytes_by_kind: dict
    count_by_kind: dict

    @property
    def weighted_bytes(self) -> float:
        return sum(COLL_WIRE[k] * b for k, b in self.bytes_by_kind.items())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum result-buffer sizes of every collective in the module text.
    Async pairs appear as -start/-done; each op is counted once (at
    -start), since the -done line repeats the buffer."""
    bytes_by: dict = {k: 0 for k in COLLECTIVES}
    count_by: dict = {k: 0 for k in COLLECTIVES}
    for comp in parse_module(hlo_text).values():
        for op in comp.ops:
            base = base_kind(op.kind)
            if base not in COLLECTIVES or op.kind.endswith("-done"):
                continue
            bytes_by[base] += op.bytes
            count_by[base] += 1
    return CollectiveStats(bytes_by_kind=bytes_by, count_by_kind=count_by)
