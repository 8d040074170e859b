"""From a profiler trace (`.xplane.pb`) to the numbers the readers need.

The JAX profiler writes one XSpace per traced window.  Its device planes
(`/device:TPU:<n>`) hold a line of executions of compiled programs
("XLA Modules") and a line of the operations inside them ("XLA Ops"); the
host planes hold the benchmark's own spans (`jax.profiler.TraceAnnotation`)
on the same clock.  `reduce_file` keeps, inside the span `bench.window`:

  busy      the union of the device's operation intervals, averaged over
            the chips that ran anything
  modules   device seconds and executions per program, by its name with
            the trailing "(<id>)" removed (the engine's chunk step is
            `jit_step`, its refill `jit_refill`)
  ops       device self seconds per operation (less the operations
            nested in it, as a loop's body is in the loop), named
            "<program>/<operation> <result type>"
  spans     the benchmark's host spans by name, as (start, end) in seconds
  events    the program's own host spans (`msc.*`) by name, as (start,
            end, stats), over the whole trace
  programs  the first chip's program executions, as (start, end, name),
            over the whole trace
  gaps      the idle stretches between device operations, each labelled
            with the program it fell inside; a stretch between programs
            is split by the innermost `msc.*` span the host was in,
            "<span>, before <program that ran next>", and a part under
            no such span takes the benchmark's span it fell in instead

On a TPU v5 lite the device's timestamps sit about a millisecond before
the host spans that dispatched them (a chunk step's execution starts
~0.9 ms before its `engine.step` span); the overlaps below carry that
error.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW = "bench.window"
HOST_SPANS = ("engine.step", "engine.submit")
PROGRAM_SPANS = "msc."
_ID = re.compile(r"\(\d+\)$")
_OP = re.compile(r"^%?([^ =]+) = (\S+)")


def program_name(name: str) -> str:
    return _ID.sub("", name)


def op_name(hlo: str) -> str:
    """"%fusion.62 = f32[4,400,1,400]{...} fusion(...)" -> "fusion.62
    f32[4,400,1,400]"; a tuple-typed result keeps only the name."""
    m = _OP.match(hlo)
    if not m:
        return hlo[:60]
    shape = m.group(2).split("{")[0]
    return m.group(1) if shape.startswith("(") else f"{m.group(1)} {shape}"


def self_times(ops) -> List[Tuple[float, float, str, float]]:
    """(start, end, name, self seconds) of sorted (start, end, name) events,
    where an event nested in another (a loop's body) is taken out of the
    outer one's time."""
    out: List[list] = []
    stack: List[int] = []
    for s, e, name in sorted(ops, key=lambda x: (x[0], -x[1])):
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= out[stack[-1]][1]:
            out[stack[-1]][3] -= e - s
        out.append([s, e, name, e - s])
        stack.append(len(out) - 1)
    return [tuple(x) for x in out]


def union(intervals) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged, lo: float, hi: float) -> float:
    """Length of [lo, hi] covered by disjoint sorted intervals."""
    i = max(0, bisect.bisect_right(merged, (lo, float("inf"))) - 1)
    total = 0.0
    for s, e in merged[i:]:
        if s >= hi:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]
    busy: List[Tuple[float, float]]          # union over the first chip
    busy_s: float                            # averaged over chips
    modules: Dict[str, Tuple[int, float]]    # name -> (executions, s)
    ops: Dict[str, float]
    spans: Dict[str, List[Tuple[float, float]]]
    gaps: List[Tuple[str, float]]
    events: Dict[str, List[Tuple[float, float, dict]]] = dataclasses.field(
        default_factory=dict)
    programs: List[Tuple[float, float, str]] = dataclasses.field(
        default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def program(self, name: str) -> Tuple[int, float]:
        return self.modules.get(name, (0, 0.0))

    def host_self_s(self, names=HOST_SPANS) -> float:
        """Seconds inside the named host spans during which the device
        ran nothing."""
        total = 0.0
        for name in names:
            for s, e in self.spans.get(name, []):
                total += (e - s) - overlap(self.busy, s, e)
        return total

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        by_label: Dict[str, float] = collections.defaultdict(float)
        count: Dict[str, int] = collections.defaultdict(int)
        for label, sec in self.gaps:
            by_label[label] += sec
            count[label] += 1
        gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[f"{n} (x{count[n]})", s] for n, s in gaps]}


def _label(spans, t: float) -> str:
    for name, ivs in spans.items():
        i = bisect.bisect_right(ivs, (t, float("inf"))) - 1
        if i >= 0 and ivs[i][0] <= t <= ivs[i][1]:
            return name
    return "outside the engine"


def innermost(events) -> List[Tuple[float, float, str]]:
    """Disjoint, sorted (start, end, name) stretches of the innermost of
    nested (start, end, name) spans; a stretch under none is left out."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, float, str]] = []
    cursor = float("-inf")

    def emit(upto):
        nonlocal cursor
        if stack and upto > cursor:
            out.append((cursor, upto, stack[-1][2]))
        cursor = max(cursor, upto)

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append((s, e, name))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def _split(lo: float, hi: float, inner, starts) -> List[Tuple[float, float,
                                                              str]]:
    """[lo, hi] cut at the edges of the innermost program spans: (start,
    end, span name, or None where no span is open) pieces."""
    pieces: List[Tuple[float, float, str]] = []
    t = lo
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    for s, e, name in inner[i:]:
        if s >= hi:
            break
        if e <= t:
            continue
        if s > t:
            pieces.append((t, s, None))
        pieces.append((max(s, t), min(e, hi), name))
        t = min(e, hi)
    if t < hi:
        pieces.append((t, hi, None))
    return pieces


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: Dict[str, List[Tuple[float, float]]] = collections.defaultdict(list)
    events: Dict[str, list] = collections.defaultdict(list)
    window = None
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                     program_name(ev.name))
                    for ev in lines.get("XLA Modules", [])]
            ops = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                    ev.name) for ev in lines.get("XLA Ops", [])]
            if mods or ops:
                devices.append((sorted(mods), sorted(ops)))
            continue
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns * 1e-9,
                      (ev.start_ns + ev.duration_ns) * 1e-9)
                if ev.name == WINDOW:
                    window = iv
                elif ev.name in HOST_SPANS:
                    spans[ev.name].append(iv)
                elif ev.name.startswith(PROGRAM_SPANS):
                    events[ev.name].append(iv + (dict(ev.stats),))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span in the trace")
    lo, hi = window
    spans = {k: sorted(v) for k, v in spans.items()}
    events = {k: sorted(v, key=lambda x: x[:2]) for k, v in events.items()}
    inner = innermost((s, e, name) for name, evs in events.items()
                      for s, e, _ in evs)
    inner_starts = [s for s, _, _ in inner]
    if not devices:
        raise ValueError(f"{path}: no device operations in the trace")

    busy_all, modules, ops_s = [], {}, collections.defaultdict(float)
    for d, (mods, ops) in enumerate(devices):
        busy = union((max(s, lo), min(e, hi)) for s, e, _ in ops
                     if e > lo and s < hi)
        busy_all.append(busy)
        if d:
            continue
        for s, e, name in mods:
            if lo <= s and e <= hi:
                n, sec = modules.get(name, (0, 0.0))
                modules[name] = (n + 1, sec + (e - s))
        starts = [m[0] for m in mods]
        for s, e, name, own in self_times(ops):
            if not (lo <= s and e <= hi):
                continue
            i = bisect.bisect_right(starts, s) - 1
            owner = mods[i][2] if i >= 0 and s <= mods[i][1] else "?"
            ops_s[f"{owner}/{op_name(name)}"] += own
    first = busy_all[0]
    gaps = []
    edges = [(lo, lo)] + first + [(hi, hi)]
    mods0 = devices[0][0]
    starts = [m[0] for m in mods0]
    for (_, prev_end), (nxt_start, _) in zip(edges, edges[1:]):
        if nxt_start - prev_end <= 0:
            continue
        j = bisect.bisect_right(starts, nxt_start + 1e-9) - 1
        if j >= 0 and mods0[j][0] <= prev_end and mods0[j][1] >= nxt_start:
            gaps.append((f"inside {mods0[j][2]}", nxt_start - prev_end))
            continue
        nxt = (mods0[j][2] if j >= 0 and mods0[j][1] >= nxt_start
               else "the window's end" if nxt_start >= hi else "?")
        for s, e, name in _split(prev_end, nxt_start, inner, inner_starts):
            where = name or _label(spans, 0.5 * (s + e))
            gaps.append((f"{where}, before {nxt}", e - s))
    busy_s = sum(sum(e - s for s, e in b) for b in busy_all) / len(busy_all)
    return Reduced(window=window, busy=first, busy_s=busy_s, modules=modules,
                   ops=dict(ops_s), spans=spans, gaps=gaps, events=events,
                   programs=mods0)


def reduce_dir(directory: str) -> Reduced:
    """The trace the profiler wrote under `directory`."""
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_file(found[-1])
