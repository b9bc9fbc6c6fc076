"""Reduce a profiler trace to the benchmark's device numbers.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain lists; ``reduce`` does the arithmetic on those lists, so a test can
check it on a synthetic trace without a chip or libtpu.

* busy: the union of the intervals in which a leaf operation ran on a
  device, clipped to the window, averaged over the devices.  The ops
  line nests a loop's body ops inside the ``while`` op that runs them;
  only ops that hold no other op count, so the gaps between the steps of
  a device loop count as idle;
* idle share: 1 - busy / window;
* collective share: the part of device 0's busy time spent in collective
  operations (all-gather, all-reduce, ...);
* device ops: self seconds (less the ops nested inside) per operation
  kind, largest first; a kind is the HLO instruction's name without its
  number (``%fusion.12 = ...`` -> ``fusion``);
* idle gaps: device 0's idle time inside the window, summed by the
  ``bench.*`` host span (what the harness's thread was doing) that it
  overlaps.

The window is the host span named ``WINDOW_SPAN``, which the harness opens
around its measured window; the profiler puts host and device events on
one clock.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv",
                        re.IGNORECASE)
TOP = 10


@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, end_ns) tuples."""
    devices: dict            # device plane name -> list of op events
    host_spans: list         # bench.* spans from the host threads


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float            # leaf ops, averaged over devices
    idle_share: float        # %
    collective_share: float | None   # % of device 0's busy time
    device_ops: list         # [[name, seconds], ...] (TOP)
    idle_gaps: list          # [[host span, seconds], ...] (TOP)
    n_devices: int


def read_xplane(path: str | Path) -> Trace:
    """Device op events and the harness's host spans of one trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, spans = {}, []

    def events(lines):
        out = []
        for ln in lines:
            for e in ln.events:
                s = int(e.start_ns)
                out.append((e.name, s, s + int(e.duration_ns)))
        return out

    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = list(plane.lines)
            ops = events(ln for ln in lines if ln.name == OPS_LINE)
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = int(e.start_ns)
                        spans.append((e.name, s, s + int(e.duration_ns)))
    return Trace(devices=devices, host_spans=spans)


def op_kind(name: str) -> str:
    """``%dynamic-slice_fusion.14 = f32[...] fusion(...)`` ->
    ``dynamic-slice_fusion``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head) or name[:64]


def leaves(events: list) -> list:
    """(name, start, end, self_ns, is_leaf) of every event of one nested
    line: self_ns is its own time less that of the events nested in it,
    is_leaf whether it holds none."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= e - s
            stack[-1][4] = False
        stack.append([name, s, e, e - s, True])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def union(intervals, lo: int, hi: int) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(merged: list, lo: int, hi: int) -> list:
    """Complement of merged intervals inside [lo, hi)."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _segments(spans: list) -> list:
    """The harness's host spans inside the window as disjoint, sorted
    (start, end, name) segments; the harness's thread opens them one
    after another, so a later span is clipped where an earlier one ran."""
    out, t = [], None
    for name, s, e in sorted((sp for sp in spans if sp[0] != WINDOW_SPAN),
                             key=lambda sp: sp[1]):
        s = s if t is None else max(s, t)
        if e > s:
            out.append((s, e, name))
            t = e
    return out


def _gaps_by_span(idle: list, segments: list) -> dict:
    """Idle time split over the host segments it overlaps (a sweep over
    both sorted lists); idle time outside every segment goes to
    ``no bench span``."""
    by_span = defaultdict(float)
    j = 0
    for s, e in idle:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        covered, k = 0, j
        while k < len(segments) and segments[k][0] < e:
            lo, hi = max(s, segments[k][0]), min(e, segments[k][1])
            if hi > lo:
                by_span[segments[k][2]] += hi - lo
                covered += hi - lo
            k += 1
        if e - s > covered:
            by_span["no bench span"] += e - s - covered
    return by_span


def reduce(trace: Trace) -> Summary:
    """The window's busy, idle, collective and top-op numbers."""
    windows = [(s, e) for n, s, e in trace.host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    if not trace.devices:
        raise ValueError("trace has no device events: nothing ran on the "
                         "device in the window")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    window_ns = hi - lo
    names = sorted(trace.devices)
    busy, per_op, leaf0 = [], defaultdict(float), None
    for name in names:
        tree = [ev for ev in leaves(trace.devices[name])
                if ev[2] > lo and ev[1] < hi]
        leaf = [(op, s, e) for op, s, e, _, is_leaf in tree if is_leaf]
        leaf0 = leaf if leaf0 is None else leaf0
        busy.append(sum(e - s for s, e in
                        union(((s, e) for _, s, e in leaf), lo, hi)))
        for op, s, e, self_ns, _ in tree:
            inside = (min(e, hi) - max(s, lo)) / (e - s) if e > s else 0.0
            per_op[op_kind(op)] += self_ns * inside / len(names)
    merged0 = union(((s, e) for _, s, e in leaf0), lo, hi)
    coll = union(((s, e) for op, s, e in leaf0 if COLLECTIVE.search(op)),
                 lo, hi)
    coll_ns = sum(e - s for s, e in coll)
    by_span = _gaps_by_span(gaps(merged0, lo, hi),
                            _segments(trace.host_spans))
    mean_busy = sum(busy) / len(busy)
    return Summary(
        window_s=window_ns / 1e9,
        busy_s=mean_busy / 1e9,
        idle_share=100.0 * (1.0 - mean_busy / window_ns),
        collective_share=(100.0 * coll_ns / busy[0]) if busy[0] else None,
        device_ops=[[op, ns / 1e9] for op, ns in
                    sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        idle_gaps=[[sp, ns / 1e9] for sp, ns in
                   sorted(by_span.items(), key=lambda kv: -kv[1])[:TOP]],
        n_devices=len(names))


def find_xplane(directory: str | Path) -> Path:
    """The one ``.xplane.pb`` that a trace into ``directory`` wrote."""
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{directory}, found {len(found)}")
    return found[0]
