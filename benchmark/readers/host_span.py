"""Reader `host_span` (source `program_span`): the program's own host spans
(`r2d2.<layer>.<phase>`, r2d2_tpu/utils/profiling.SPANS), read from the host
plane of the traced window's .xplane.pb: the profiler's own trace, so the
spans share the device events' clock. The file is parsed once per run.

spec: {"span": <regex>, "minus": <regex of child spans, optional>,
       "stat": <a span id to sum instead of the duration, optional>,
       "per": <regex of the spans to divide by; default the dispatches>,
       "scale": <ns (or the stat's unit) -> the metric's unit>}
  or  {"span": <regex>, "running": <id>, "over_running": <id>, "scale"}: the
      ids are running totals the program stamps on each span as it opens;
      the window's amount is the last span's less the first's, and the
      metric is the ratio of the two amounts.
  or  {"idle_share": true}: the share of the first device's idle time in the
      window that some program span covers, in percent; the table by
      innermost span goes to a progress line.

A mean per dispatch divides by the number of `r2d2.dispatch` spans this reader
found in the window itself: the count taken where the work happens. Nothing to
read (None): no trace, or a program without the facility (a parent commit).
A trace without any matching span reads 0.0 and says so."""

from __future__ import annotations

import os
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

from benchmark import trace as tr

PREFIX = "r2d2."
DISPATCH = r"^r2d2\.dispatch$"


class Span(NamedTuple):
    name: str
    start: float  # ns
    dur: float    # ns
    line: int     # host thread
    stats: Dict[str, object]

    @property
    def end(self) -> float:
        return self.start + self.dur


_parsed: Dict[str, List[Span]] = {}  # .xplane.pb path -> its program spans


def load_spans(path: str, host_plane: str) -> List[Span]:
    from jax.profiler import ProfileData

    host_re = re.compile(host_plane)
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not host_re.search(plane.name):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(e.name, float(e.start_ns), float(e.duration_ns), i, dict(e.stats)))
    out.sort(key=lambda s: (s.start, -s.dur))
    return out


def program_has_spans() -> bool:
    try:
        from r2d2_tpu.utils import profiling
    except ImportError:
        return False
    return hasattr(profiling, "SPANS")


def spans_of(ctx) -> Optional[List[Span]]:
    """The traced window's program spans; None where there is nothing to read
    from, [] where there is a trace but no .xplane.pb or no span in it."""
    if ctx.trace_data is None or not ctx.trace_data.ops or not program_has_spans():
        return None
    trace_dir = os.path.join(ctx.cell.root, ".benchmark_work", "trace", ctx.cell.name)
    try:
        path = tr.find_xplane(trace_dir)
    except FileNotFoundError:
        return []
    if path not in _parsed:
        _parsed.clear()
        _parsed[path] = load_spans(path, ctx.patterns["host_plane"])
    return _parsed[path]


def innermost_segments(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """Disjoint sorted (start, end, name) pieces of ONE thread's spans (sorted
    by start, longer first), each piece named after the innermost span over it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Span] = []
    cursor = 0.0  # pieces are emitted up to here

    def close(upto: float) -> None:
        nonlocal cursor
        while stack and stack[-1].end <= upto:
            top = stack.pop()
            if top.end > cursor:
                out.append((cursor, top.end, top.name))
                cursor = top.end

    for s in spans:
        close(s.start)
        if stack and s.start > cursor:
            out.append((cursor, s.start, stack[-1].name))
        cursor = s.start
        stack.append(s)
    close(float("inf"))
    return out


def overlap_by_name(idle, segments) -> Dict[str, float]:
    """ns of the disjoint sorted `idle` intervals under each name of the
    disjoint sorted (start, end, name) `segments`: one pass over both."""
    acc: Dict[str, float] = {}
    j = 0
    for a, b, name in segments:
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < b:
            acc[name] = acc.get(name, 0.0) + min(b, idle[k][1]) - max(a, idle[k][0])
            k += 1
    return acc


def idle_by_innermost_span(trace: tr.Trace, spans: List[Span]) -> Tuple[float, float, Dict[str, float]]:
    """-> (idle ns of the first device in the window, the part of it that some
    program span covers, {innermost span: idle ns under it})."""
    dev = sorted(trace.ops)[0]
    idle = tr.gaps(tr.busy_intervals(trace.ops[dev]), tr.window_of(trace))
    by_name: Dict[str, float] = {}
    for line in sorted({s.line for s in spans}):
        one = overlap_by_name(idle, innermost_segments([s for s in spans if s.line == line]))
        for name, ns in one.items():
            by_name[name] = by_name.get(name, 0.0) + ns
    covered = tr.total(idle) - tr.total(tr.subtract(idle, tr.union((s.start, s.end) for s in spans)))
    return tr.total(idle), covered, by_name


def read(spec, ctx):
    spans = spans_of(ctx)
    if spans is None:
        return None
    name = spec.get("name", "?")
    if spec.get("idle_share"):
        if not spans:
            print(f"[bench] {name}: no program span in the traced window", flush=True)
            return 0.0
        idle, covered, by_name = idle_by_innermost_span(ctx.trace_data, spans)
        table = ", ".join(f"{k} {v / 1e6:.3f} ms" for k, v in sorted(by_name.items(), key=lambda kv: -kv[1]))
        print(f"[bench] {name}: first device idle {idle / 1e6:.3f} ms, {covered / 1e6:.3f} ms of it under "
              f"a program span; by innermost span: {table or 'none'}", flush=True)
        return 100.0 * covered / idle if idle > 0 else 0.0
    want = re.compile(spec["span"])
    if spec.get("running"):
        matching = [s for s in spans if want.search(s.name)]
        grown = lambda key: float(matching[-1].stats.get(key, 0)) - float(matching[0].stats.get(key, 0))
        if not matching or not grown(spec["over_running"]):
            print(f"[bench] {name}: {spec['over_running']!r} did not grow over the {len(matching)} "
                  f"span(s) matching {want.pattern!r} in the traced window", flush=True)
            return 0.0
        return grown(spec["running"]) * float(spec.get("scale", 1.0)) / grown(spec["over_running"])
    minus = re.compile(spec["minus"]) if spec.get("minus") else None
    per = re.compile(spec.get("per", DISPATCH))
    stat = spec.get("stat")
    amount = lambda s: float(s.stats.get(stat, 0)) if stat else s.dur
    total = sum(amount(s) for s in spans if want.search(s.name))
    if minus is not None:  # a child that falls outside its parent shows as a negative reading
        total -= sum(amount(s) for s in spans if minus.search(s.name))
    n = sum(1 for s in spans if per.search(s.name))
    if not n:
        print(f"[bench] {name}: no span matches {per.pattern!r} in the traced window", flush=True)
        return 0.0
    return total * float(spec.get("scale", 1.0)) / n
