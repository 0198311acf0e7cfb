"""Reader `trace_scope` (source `device_trace`): device time by the program's
own scopes. Every device event of the traced window goes to the program whose
execution on the module line contains it, its instruction name to an op_name
through that program's scope map (r2d2_tpu/utils/profiling.program_scopes,
asked for here, after the window), and its SELF time to the first matching
bucket of benchmark/trace_scopes.json, else to `unscoped`. The buckets and
`unscoped` add up to the summed self time of the device's events, which is its
busy time; a container (`while`) keeps only its self time.

spec: {"bucket": <name>, "per": "updates", "scale": 1000.0}   time per update
  or  {"bucket": "unscoped", "share": true}                   % of busy time
  or  {"op_name": <regex>, "within": <bucket>, "per": "updates", "scale": 1000.0}
      a scope of the layer file's own: the self time of the events that the
      ordered buckets gave to `within` and whose op_name the regex finds. A new
      kind of layer inside the core (an attention, an expert layer) gets its
      metric as a layer file and a manifest entry; trace_scopes.json is shared
      by every cell and is not edited for it. The regex sees the whole op_name
      (flax module paths, `jit(<scope>)` of profiling.scoped); `.` is the bucket.

Nothing to read (None): no trace, or a program without the facility (a parent
commit). With no registered program every event is unscoped: a bucket reads
0.0 and the share 100, and a progress line says so."""

from __future__ import annotations

import json
import os
import re
import time
from typing import Dict, List, Optional, Tuple

from benchmark import harness
from benchmark import trace as tr
from benchmark.readers import scaled

UNSCOPED = "unscoped"
TOP = 12  # members of a bucket named on its progress line
_done: Dict[int, dict] = {}  # id(trace) -> attribution of that trace


def load_scopes(bench_dir: str) -> dict:
    return harness.load_json(os.path.join(bench_dir, "trace_scopes.json"))


def program_maps() -> Optional[Dict[str, Dict[str, str]]]:
    """{registered step program: {instruction: op_name}}; None without the facility."""
    try:
        from r2d2_tpu.utils import profiling

        names = profiling.registered_programs()
    except (ImportError, AttributeError):
        return None
    maps = {}
    for name in names:
        t = time.perf_counter()
        maps[name] = profiling.program_scopes(name)
        print(f"[bench] scopes of step program {name!r}: {len(maps[name])} named instructions "
              f"in {time.perf_counter() - t:.1f}s", flush=True)
    return maps


def _executions(events: List[tr.Event], execs: List[tr.Event]) -> List[Optional[str]]:
    """For each event (sorted by start) the name of the execution on the
    module line that contains its start, or None."""
    out, j = [], 0
    for e in events:
        while j < len(execs) and execs[j].end <= e.start:
            j += 1
        inside = j < len(execs) and execs[j].start <= e.start
        out.append(execs[j].name if inside else None)
    return out


def attribute(trace: tr.Trace, maps: Dict[str, Dict[str, str]], scopes: dict) -> dict:
    """-> {"seconds": {bucket: s, mean over devices}, "busy": s,
           "top": {bucket: [(label, op_name or '', s), ...] its TOP largest members},
           "rows": [(bucket, event text, op_name or '', s), ...] every member, largest first}."""
    instr_re = re.compile(scopes["instruction"])
    buckets = [(name, re.compile(rx)) for name, rx in scopes["buckets"]]
    merged: Dict[str, str] = {}
    for m in maps.values():
        for k, v in m.items():
            merged.setdefault(k, v)
    instr_of: Dict[str, str] = {}

    def instr(name: str) -> str:
        got = instr_of.get(name)
        if got is None:
            m = instr_re.match(name)
            got = instr_of[name] = m.group(1) if m else name
        return got

    def bucket_of(op_name: Optional[str]) -> str:
        if op_name:
            for name, rx in buckets:
                if rx.search(op_name):
                    return name
        return UNSCOPED

    per_dev: List[Dict[str, float]] = []
    members: Dict[Tuple[str, str, str], float] = {}  # (bucket, event text, op_name) -> self ns
    for dev in sorted(trace.ops):
        events = trace.ops[dev]
        execs = sorted(trace.modules.get(dev, []), key=lambda e: e.start)
        where = _executions(events, execs) if execs else [""] * len(events)
        # which registered program is each execution name: the one whose
        # instructions cover most of what ran inside it (dp4's two programs
        # are both `jit_body(<fingerprint>)`)
        seen: Dict[str, set] = {}
        for e, w in zip(events, where):
            if w:
                seen.setdefault(w, set()).add(instr(e.name))
        program: Dict[str, Dict[str, str]] = {"": merged}
        for w, names in seen.items():
            best = max(maps.items(), key=lambda kv: (len(names & kv[1].keys()), f"_{kv[0]}(" in w),
                       default=(None, {}))
            program[w] = best[1] if names & best[1].keys() else {}
        verdict: Dict[Tuple[str, str], Tuple[str, str]] = {}
        acc: Dict[str, float] = {}
        for e, w in zip(events, where):
            if w is None:  # outside every execution on the module line
                b, op = UNSCOPED, ""
            else:
                key = (w, e.name)
                got = verdict.get(key)
                if got is None:
                    op = program[w].get(instr(e.name), "")
                    got = verdict[key] = (bucket_of(op), op)
                b, op = got
            acc[b] = acc.get(b, 0.0) + e.self_dur
            k = (b, e.text, op)
            members[k] = members.get(k, 0.0) + e.self_dur
        per_dev.append(acc)
    n = max(len(per_dev), 1)
    names = [b for b, _ in buckets] + [UNSCOPED]
    seconds = {b: sum(d.get(b, 0.0) for d in per_dev) / n / 1e9 for b in names}
    ranked = sorted(members.items(), key=lambda kv: -kv[1])
    rows = [(b, text, op, v / n / 1e9) for (b, text, op), v in ranked]
    top = {b: [(tr.op_label(text.split("|")[0]), op, v) for bb, text, op, v in rows if bb == b][:TOP] for b in names}
    return {"seconds": seconds, "busy": sum(seconds.values()), "top": top, "rows": rows}


def _tail(op_name: str, parts: int = 4) -> str:
    """The last few path elements of an op_name: enough to tell what it is."""
    return "/".join(op_name.split("/")[-parts:]) if op_name else "no op_name"


def _write_members(ctx, got: dict) -> None:
    """Every member of every bucket, with the categories of trace_patterns.json
    that its text matches (the cross-table of PR 22's shape regexes and the
    program's own names), to <work dir>/scopes/<cell>.json: too long for a
    progress line, and what PERF.md's accounting by instruction is made from."""
    cats = {k: tr.matcher(rx) for k, rx in ctx.patterns["categories"].items()}
    rows = [{"bucket": b, "instruction": tr.op_label(text.split("|")[0]), "op_name": op, "seconds": v,
             "categories": [k for k, found in cats.items() if found(text)]}
            for b, text, op, v in got["rows"]]
    path = os.path.join(ctx.work_dir("scopes"), ctx.cell.name + ".json")
    with open(path, "w") as fh:
        json.dump({"seconds": got["seconds"], "busy": got["busy"], "rows": rows}, fh)
    print(f"[bench] every bucket's members: {path}", flush=True)


def attribution(ctx) -> Optional[dict]:
    if ctx.trace_data is None or not ctx.trace_data.ops:
        return None
    key = id(ctx.trace_data)
    if key not in _done:
        maps = program_maps()
        if maps is None:
            return None
        _done.clear()
        got = _done[key] = attribute(ctx.trace_data, maps, load_scopes(ctx.cell.bench_dir))
        busy = got["busy"]
        union_busy = tr.busy_seconds(ctx.trace_data)[0]
        print(f"[bench] device time by scope ({len(maps)} step programs): "
              + ", ".join(f"{k} {v:.4f}s ({100 * v / busy if busy else 0:.1f}%)" for k, v in got["seconds"].items())
              + f"; sum {busy:.4f}s, union of device events {union_busy:.4f}s", flush=True)
        _write_members(ctx, got)
        for b, rows in got["top"].items():  # what each bucket is made of, for PERF.md section 5
            if rows:
                print(f"[bench] largest in {b}: "
                      + "; ".join(f"{label} [{_tail(op)}] {v:.4f}s" for label, op, v in rows), flush=True)
    return _done[key]


def seconds_within(got: dict, within: str, op_name: str, label: str = "") -> float:
    """Self time of the rows of bucket `within` whose op_name `op_name` (a
    regex) finds; says so where it finds none."""
    if within not in got["seconds"]:
        raise KeyError(f"{label or op_name}: no bucket {within!r} (have {sorted(got['seconds'])})")
    rx = re.compile(op_name)
    found = [v for b, _, op, v in got["rows"] if b == within and rx.search(op)]
    if not found:
        print(f"[bench] {label}: op_name {op_name!r} finds nothing in bucket {within!r} "
              f"({got['seconds'][within]:.4f}s): reads 0", flush=True)
    return sum(found)


def read(spec, ctx):
    got = attribution(ctx)
    if got is None:
        return None
    if "op_name" in spec:
        return scaled(spec, ctx, seconds_within(got, spec["within"], spec["op_name"], spec.get("name", "")))
    seconds = got["seconds"].get(spec["bucket"], 0.0)
    if spec.get("share"):
        return 100.0 * seconds / got["busy"] if got["busy"] > 0 else 0.0
    return scaled(spec, ctx, seconds)
