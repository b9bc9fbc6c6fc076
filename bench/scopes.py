"""Where a traced window's device time went, by the program's own names.

``bench.trace`` names a device op by its HLO kind alone; this module adds
what the program names itself, and leaves ``bench.trace`` as it is:

* program scopes: ``jax.named_scope`` names (``pcg.spmv``, ``pcg.sweep``,
  ``pcg.vector``; ``repro.core.iccg.PCG_SCOPES``) that the compiler keeps
  in each HLO instruction's ``op_name`` metadata.  A TPU trace names each
  device op by its instruction and carries no metadata, so the scopes
  come from the optimized HLO text of the executables that ran
  (``hlo_scopes``), once per distinct op name.  An op's scope is the
  innermost such name in its ``op_name``; an op without one (a copy the
  compiler inserted) takes the scope of the innermost op around it on
  the trace's nested ops line;
* program spans: the ``repro.*`` host intervals of the solve
  (``repro.core.timing``), on the same clock as the device ops.

From these ``reduce`` makes, for device 0 and inside the ``bench.window``
span:

* seconds per scope: leaf-op time that carries each scope, and the share
  of device 0's leaf-op time whose ops carry a scope of their own (not
  one taken from an op around them);
* calls per scope, anchored on the sweep's loop.  A PCG iteration runs
  one SpMV and one preconditioner apply, and an apply of the fused
  round-major sweep is one program loop of 2S steps: a device ``while``
  op whose ``op_name`` ends in ``pcg.sweep/.../while`` (the compiler's
  own loops, such as float64 emulation's, end in the op they emulate).
  So a sweep call is one such loop wholly inside the window, from its
  start to its end (the gaps between its steps count as sweep time), and
  a call of another scope is that scope's leaf-op time between two
  consecutive sweep calls (nothing where it has none).  Runs of one
  scope's ops do not count calls: the compiler interleaves the scopes'
  small ops (async copies, reshapes), which splits one call's ops into
  several runs;
* device ops as ``<scope>/<kind>`` (bare kind for ops without a scope),
  self seconds averaged over devices, as ``bench.trace`` counts them;
* idle gaps by cause: idle inside a device ``while`` op goes to
  ``device.loop``; else to the innermost ``repro.*`` span open on the
  host; else to the innermost ``bench.*`` span; else to
  ``no bench span``;
* host spans: count and seconds of each ``repro.*`` span wholly inside
  the window;
* lost events: device 0's last op ends more than ``LOSS_SHARE`` of the
  window before its end while the host was inside a solve.  The
  profiler's buffer has then dropped events: the scope numbers are not
  read (``None``), and a warning goes to standard error.

The window's busy, idle and collective numbers are ``bench.trace.reduce``
of the same events, unchanged (``base``).
"""
from __future__ import annotations

import dataclasses
import re
import statistics
import sys
from collections import defaultdict
from pathlib import Path

from bench import trace as T

#: a program scope: one path component of an op's ``op_name``
SCOPE = re.compile(r"(?:^|/)(pcg\.[a-z_]+)(?=/|$)")
SWEEP = "pcg.sweep"
PROGRAM_SPAN_PREFIX = "repro."
LOOP = "device.loop"
NO_SPAN = "no bench span"
LOSS_SHARE = 0.05
#: host spans inside which the device is meant to be at work
SOLVE_SPANS = ("repro.solve.pcg", "bench.solve")


@dataclasses.dataclass
class ScopedTrace:
    trace: T.Trace           # the events as ``bench.trace.read_xplane`` reads them
    scopes: dict             # device op name -> its scope, or None
    program_spans: list      # repro.* host spans: (name, start_ns, end_ns)
    loops: frozenset = frozenset()  # device op names of program loops


@dataclasses.dataclass
class ScopedSummary:
    base: T.Summary          # ``bench.trace.reduce`` of the same events
    scope_s: dict            # scope -> device 0 leaf seconds in the window
    scoped_share: float | None  # % of device 0's leaf time with own scope
    calls: dict              # scope -> [seconds of each whole call]
    device_ops: list         # [[<scope>/<kind> or kind, seconds], ...] (TOP)
    idle_gaps: list          # [[cause, seconds], ...] (TOP)
    host_spans: dict         # repro.* name -> [count, seconds] in the window
    lost_events: bool


def scope_of(op_name: str) -> str | None:
    """The innermost program scope in an ``op_name`` path, or None."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def hlo_scopes(texts) -> dict:
    """HLO instruction name -> (scope, whether it is a program loop),
    for the instructions with a scope in optimized HLO module texts."""
    out = {}
    for text in texts:
        for name, op_name in re.findall(
                r'^\s*(?:ROOT )?%(\S+) = .*?op_name="([^"]*)"', text, re.M):
            scope = scope_of(op_name)
            if scope:
                out[name] = (scope, op_name.endswith("/while"))
    return out


def read(path: str | Path, hlo: dict) -> ScopedTrace:
    """``bench.trace.read_xplane``'s events, with each device op's scope
    from ``hlo`` (``hlo_scopes``) and the program's host spans, in one
    pass over the file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, bench_spans, program_spans, scopes, loops = {}, [], [], {}, set()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for ln in plane.lines:
                if ln.name != T.OPS_LINE:
                    continue
                for e in ln.events:
                    name, s = e.name, int(e.start_ns)
                    if name not in scopes:
                        head = name.split(" = ", 1)[0].strip().lstrip("%")
                        scopes[name], loop = hlo.get(head, (None, False))
                        if loop:
                            loops.add(name)
                    ops.append((name, s, s + int(e.duration_ns)))
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    name = e.name
                    if name.startswith(T.SPAN_PREFIX):
                        into = bench_spans
                    elif name.startswith(PROGRAM_SPAN_PREFIX):
                        into = program_spans
                    else:
                        continue
                    s = int(e.start_ns)
                    into.append((name, s, s + int(e.duration_ns)))
    return ScopedTrace(trace=T.Trace(devices=devices, host_spans=bench_spans),
                       scopes=scopes, program_spans=program_spans,
                       loops=frozenset(loops))


def nest(events: list, scopes: dict) -> list:
    """``[name, start, end, self_ns, is_leaf, scope]`` of every event of
    one nested line, by start (``bench.trace.leaves`` with scopes): an
    event without a scope of its own takes its innermost enclosing
    event's."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        scope = scopes.get(name)
        if stack:
            stack[-1][3] -= e - s
            stack[-1][4] = False
            if scope is None:
                scope = stack[-1][5]
        rec = [name, s, e, e - s, True, scope]
        stack.append(rec)
        out.append(rec)
    return out


def calls(nested: list, loops, lo: int, hi: int) -> dict:
    """scope -> [ns of each call] of one device's ``nest`` output
    (module docstring): sweep calls are the ``SWEEP`` program loops
    (``loops``: op names) wholly inside [lo, hi); another scope's call
    is its leaf-op time between two consecutive sweep calls."""
    sweeps = []
    for op, s, e, _, _, scope in nested:
        if (scope == SWEEP and op in loops and s >= lo and e <= hi
                and not (sweeps and e <= sweeps[-1][1])):
            sweeps.append((s, e))
    out = defaultdict(list)
    out[SWEEP] = [e - s for s, e in sweeps]
    between = defaultdict(lambda: defaultdict(int))   # gap index -> scope
    j = 0
    for op, s, e, _, is_leaf, scope in nested:
        if not is_leaf or scope in (None, SWEEP):
            continue
        while j < len(sweeps) and sweeps[j][1] <= s:
            j += 1
        if 0 < j < len(sweeps) and e <= sweeps[j][0]:
            between[j][scope] += e - s
    for gap in sorted(between):
        for scope, ns in between[gap].items():
            out[scope].append(ns)
    return dict(out)


def innermost(spans: list, lo: int, hi: int) -> list:
    """Disjoint, sorted (start, end, name) segments of [lo, hi): at each
    time the span open then that started last (the shorter on a tie)."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(s, -e, name) for name, s, e in spans if s <= a and e >= b]
        if not open_:
            continue
        name = max(open_)[2]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def split(intervals: list, segments: list, into: dict) -> list:
    """Add each interval's overlap with the named segments to ``into``;
    returns the parts of the intervals no segment covers."""
    rest, j = [], 0
    for s, e in intervals:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        t, k = s, j
        while k < len(segments) and segments[k][0] < e:
            a, b = max(s, segments[k][0]), min(e, segments[k][1])
            if b > a:
                into[segments[k][2]] += b - a
                if a > t:
                    rest.append((t, a))
                t = max(t, b)
            k += 1
        if e > t:
            rest.append((t, e))
    return rest


def _lost_events(ops0: list, spans: list, lo: int, hi: int) -> bool:
    """Device 0 went quiet over the window's last ``LOSS_SHARE`` or more
    while the host was in a solve: a solve span covers that stretch, or
    no host span ends in it (the host sat in one the profiler never
    closed: a solve still running when it stopped)."""
    last = max(e for _, _, e in ops0)
    if hi - last <= LOSS_SHARE * (hi - lo):
        return False
    tail = [(name, s, e) for name, s, e in spans if s < hi and e > last]
    return (any(name in SOLVE_SPANS for name, _, _ in tail)
            or not any(e < hi for _, _, e in tail))


def reduce(st: ScopedTrace) -> ScopedSummary:
    """``bench.trace.reduce`` and the scope numbers of the module
    docstring."""
    base = T.reduce(st.trace)
    windows = [(s, e) for n, s, e in st.trace.host_spans
               if n == T.WINDOW_SPAN]
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    names = sorted(st.trace.devices)
    per_op, nested0 = defaultdict(float), None
    for name in names:
        tree = nest(st.trace.devices[name], st.scopes)
        nested0 = tree if nested0 is None else nested0
        for op, s, e, self_ns, _, scope in tree:
            if e <= lo or s >= hi:
                continue
            inside = (min(e, hi) - max(s, lo)) / (e - s) if e > s else 0.0
            kind = T.op_kind(op)
            key = f"{scope}/{kind}" if scope else kind
            per_op[key] += self_ns * inside / len(names)

    leaf0 = [(op, s, e, scope) for op, s, e, _, is_leaf, scope in nested0
             if is_leaf]
    scope_ns, leaf_ns, own_ns = defaultdict(int), 0, 0
    for op, s, e, scope in leaf0:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            leaf_ns += d
            if scope:
                scope_ns[scope] += d
            if st.scopes.get(op):
                own_ns += d
    idle = T.gaps(T.union(((s, e) for _, s, e, _ in leaf0), lo, hi), lo, hi)
    whiles = T.union(((s, e) for op, s, e, _, _, _ in nested0
                      if T.op_kind(op).startswith("while")), lo, hi)
    by_cause = defaultdict(float)
    rest = split(idle, [(s, e, LOOP) for s, e in whiles], by_cause)
    rest = split(rest, innermost(st.program_spans, lo, hi), by_cause)
    bench_spans = [sp for sp in st.trace.host_spans if sp[0] != T.WINDOW_SPAN]
    rest = split(rest, innermost(bench_spans, lo, hi), by_cause)
    if rest:
        by_cause[NO_SPAN] += sum(e - s for s, e in rest)

    host = defaultdict(lambda: [0, 0.0])
    for name, s, e in st.program_spans:
        if s >= lo and e <= hi:
            host[name][0] += 1
            host[name][1] += (e - s) / 1e9

    lost = _lost_events(st.trace.devices[names[0]],
                        st.program_spans + bench_spans, lo, hi)
    if lost:
        print("bench.scopes: device 0's ops stop before the window's end "
              "while a solve runs: the trace lost events; scope readings "
              "are left out", file=sys.stderr)
    found = calls(nested0, st.loops, lo, hi)
    return ScopedSummary(
        base=base,
        scope_s={k: v / 1e9 for k, v in sorted(scope_ns.items())},
        scoped_share=100.0 * own_ns / leaf_ns if leaf_ns else None,
        calls={k: [ns / 1e9 for ns in v] for k, v in sorted(found.items())
               if v},
        device_ops=[[k, ns / 1e9] for k, ns in
                    sorted(per_op.items(), key=lambda kv: -kv[1])[:T.TOP]],
        idle_gaps=[[k, ns / 1e9] for k, ns in
                   sorted(by_cause.items(), key=lambda kv: -kv[1])[:T.TOP]],
        host_spans={k: v for k, v in sorted(host.items())},
        lost_events=lost)


def median_call_s(summary: ScopedSummary, scope: str) -> float | None:
    """Median seconds of a scope's whole calls (one stall of the device
    moves the median of a window's calls, not its typical call); None
    where the window holds none, the ops carry no scope, or the trace
    lost events."""
    found = summary.calls.get(scope)
    if summary.lost_events or not found:
        return None
    return statistics.median(found)
