"""From a jax profiler trace to numbers: the reduction every PR shares.

`load` turns an `.xplane.pb` into plain event lists (one per device, plus the
benchmark's own host spans); everything after that works on those lists, so
the arithmetic is testable without a profiler (tests/benchmark feeds it
synthetic events and a recorded fixture). All times are nanoseconds on the
trace's own clock until a function says seconds.

Definitions (on-chip-measurement guide, section 4):
- busy: the union of the intervals in which an operation ran on the device;
  idle share = 1 - busy / window.
- a category's time: the summed SELF time of the device events whose text
  matches the category's pattern. Self time = duration minus the part covered
  by events nested inside it, so a `while` op and the fusions inside it are
  not counted twice.
- exposed collective time: the part of the collectives' intervals during
  which no other (non-container) operation ran on that device.
- dispatch gap: idle time between one execution of a step program ending and
  the next starting, on the module line.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple


class Event(NamedTuple):
    name: str
    start: float      # ns
    dur: float        # ns
    text: str         # name + string stats, what patterns are searched in
    self_dur: float = -1.0   # ns not covered by nested events (with_self_times)

    @property
    def end(self) -> float:
        return self.start + self.dur


class Trace(NamedTuple):
    ops: Dict[str, List[Event]]       # device plane name -> op events
    modules: Dict[str, List[Event]]   # device plane name -> program executions
    host: List[Event]                 # the benchmark's own TraceAnnotation spans


def load_patterns(path: Optional[str] = None) -> dict:
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)), "trace_patterns.json")
    with open(path) as fh:
        return json.load(fh)


def find_xplane(trace_dir: str) -> str:
    """The newest .xplane.pb under a jax.profiler trace directory."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def event_text(name: str, stats: Iterable[Tuple[str, object]]) -> str:
    return "|".join([name, *(v for _, v in stats if isinstance(v, str))])


def load(path: str, patterns: dict) -> Trace:
    """Read an .xplane.pb with jax's own reader (no other dependency)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    dev_re = re.compile(patterns["device_plane"])
    host_re = re.compile(patterns["host_plane"])
    prefixes = tuple(patterns["host_span_prefixes"])
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    op_re = re.compile("|".join(patterns["op_lines"]))
    mod_re = re.compile("|".join(patterns["module_lines"]))
    texts: Dict[str, str] = {}  # event name -> name + its string stats (of the name's first event)
    for plane in data.planes:
        if dev_re.search(plane.name):
            for line in plane.lines:
                if op_re.search(line.name):
                    dest = ops.setdefault(plane.name, [])
                elif mod_re.search(line.name):
                    dest = modules.setdefault(plane.name, [])
                else:
                    continue
                for e in line.events:
                    name = e.name
                    text = texts.get(name)
                    if text is None:  # a dp4 trace holds millions of events of a few thousand names
                        text = texts[name] = event_text(name, e.stats)
                    dest.append(Event(name, float(e.start_ns), float(e.duration_ns), text))
        if host_re.search(plane.name):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefixes):
                        host.append(Event(e.name, float(e.start_ns), float(e.duration_ns), e.name))
    for evs in (*modules.values(), host):
        evs.sort(key=lambda e: (e.start, -e.dur))
    return Trace({d: with_self_times(evs) for d, evs in ops.items()}, modules, host)


# ------------------------------------------------------------------ intervals


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]):
    """The parts of disjoint sorted `a` not covered by disjoint sorted `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_of(trace: Trace) -> Tuple[float, float]:
    """First device event's start to the last one's end, over all devices."""
    evs = [e for lst in trace.ops.values() for e in lst]
    if not evs:
        raise ValueError("the trace holds no device operation")
    return min(e.start for e in evs), max(e.end for e in evs)


def busy_intervals(events: Sequence[Event]) -> List[Tuple[float, float]]:
    return union((e.start, e.end) for e in events)


def gaps(busy: Sequence[Tuple[float, float]], window: Tuple[float, float]):
    """Idle (start, end) intervals inside the window."""
    return subtract([window], busy)


def self_times(events: Sequence[Event]) -> List[float]:
    """Self time of each event of ONE line (sorted by start, longer first):
    its duration minus the time covered by events nested inside it."""
    out = [e.dur for e in events]
    stack: List[int] = []
    for i, e in enumerate(events):
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            out[stack[-1]] -= min(e.end, events[stack[-1]].end) - e.start
        stack.append(i)
    return [max(x, 0.0) for x in out]


def with_self_times(events: Iterable[Event]) -> List[Event]:
    """The events of one line, sorted, each carrying its self time."""
    evs = sorted(events, key=lambda e: (e.start, -e.dur))
    return [e._replace(self_dur=st) for e, st in zip(evs, self_times(evs))]


# ------------------------------------------------------------------ reductions


def per_device(trace: Trace, fn) -> List[float]:
    return [fn(trace.ops[d]) for d in sorted(trace.ops)]


def busy_seconds(trace: Trace) -> Tuple[float, float, List[float]]:
    """-> (mean busy s over devices, window s, idle share of each device)."""
    w = window_of(trace)
    span = max(w[1] - w[0], 1.0)
    busy = per_device(trace, lambda evs: total(busy_intervals(evs)))
    return sum(busy) / len(busy) / 1e9, span / 1e9, [1.0 - b / span for b in busy]


def matcher(pattern: str):
    """text -> whether `pattern` is found in it, remembered per distinct text
    (a trace repeats a few thousand instructions millions of times)."""
    rx, seen = re.compile(pattern), {}

    def found(text: str) -> bool:
        hit = seen.get(text)
        if hit is None:
            hit = seen[text] = rx.search(text) is not None
        return hit

    return found


def category_seconds(trace: Trace, pattern: str) -> float:
    """Summed self time of matching device events, mean over devices."""
    found = matcher(pattern)

    def one(evs):
        return sum(e.self_dur for e in evs if found(e.text))

    vals = per_device(trace, one)
    return sum(vals) / len(vals) / 1e9


def exposed_seconds(trace: Trace, collective: str, container: str) -> float:
    """Collective time not overlapped by other work on the same device, mean
    over devices."""
    is_coll, is_container = matcher(collective), matcher(container)

    def one(evs):
        coll = union((e.start, e.end) for e in evs if is_coll(e.text))
        other = union((e.start, e.end) for e in evs
                      if not is_coll(e.text) and not is_container(e.name))
        return total(subtract(coll, other))

    vals = per_device(trace, one)
    return sum(vals) / len(vals) / 1e9


def module_gaps_ms(trace: Trace, pattern: str) -> List[float]:
    """Idle ms between consecutive executions of the programs matching
    `pattern`, on the first device's module line."""
    rx = re.compile(pattern)
    for dev in sorted(trace.modules):
        runs = [e for e in trace.modules[dev] if rx.search(e.text)]
        return [max(b.start - a.end, 0.0) / 1e6 for a, b in zip(runs, runs[1:])]
    return []


_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")


def op_label(name: str) -> str:
    """A short label for a device event: an HLO instruction keeps its own
    name and its first output shape ('copy.187 u8[1280,441,84,84,1]'); any
    other name has its instance number folded (fusion.12 -> fusion)."""
    if " = " in name:
        head, rest = name.split(" = ", 1)
        shape = _SHAPE.search(rest)
        return head.lstrip("%") + (" " + shape.group(0) if shape else "")
    return re.sub(r"[.\d]+$", "", name.lstrip("%")) or name


def top_ops(trace: Trace, container: str, n: int = 10) -> List[List[object]]:
    """[[label, seconds], ...]: device operations by summed self time on the
    busiest device (containers such as `while` keep only their self time)."""
    if not trace.ops:
        return []
    dev = max(trace.ops, key=lambda d: total(busy_intervals(trace.ops[d])))
    by_name: Dict[str, float] = {}
    for e in trace.ops[dev]:
        by_name[e.name] = by_name.get(e.name, 0.0) + e.self_dur
    acc: Dict[str, float] = {}
    for name, v in by_name.items():
        key = op_label(name)
        acc[key] = acc.get(key, 0.0) + v
    return [[k, v / 1e9] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps_by_host_span(trace: Trace, n: int = 10, longest: int = 200) -> List[List[object]]:
    """[[host span name, seconds], ...]: the first device's `longest` idle
    gaps, each attributed to the host span that covers most of it, of those
    that cover it alike the innermost (the program's `r2d2.` spans nest inside
    the benchmark's `bench.step`; "unattributed" where none does), summed by
    name."""
    if not trace.ops:
        return []
    dev = sorted(trace.ops)[0]
    idle = gaps(busy_intervals(trace.ops[dev]), window_of(trace))
    idle = sorted(idle, key=lambda g: g[0] - g[1])[:longest]
    acc: Dict[str, float] = {}
    for s, e in idle:
        best, cover = "unattributed", 0.0
        for h in trace.host:
            if h.start >= e:
                break
            ov = min(e, h.end) - max(s, h.start)
            if ov > 0.0 and ov >= cover:  # sorted outermost first: a tie goes inwards
                best, cover = h.name, ov
        acc[best] = acc.get(best, 0.0) + (e - s)
    return [[k, v / 1e9] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def summarize_names(trace: Trace, limit: int = 60) -> List[dict]:
    """For reading a trace by hand: distinct event texts by self time."""
    acc: Dict[str, List[float]] = {}
    for evs in trace.ops.values():
        for e in evs:
            row = acc.setdefault(e.text[:300], [0.0, 0])
            row[0] += e.self_dur
            row[1] += 1
    rows = sorted(acc.items(), key=lambda kv: -kv[1][0])[:limit]
    return [{"text": k, "self_ms": v[0] / 1e6, "count": v[1]} for k, v in rows]
