"""The host side of a dispatch as a table: what the program's own spans
(`r2d2_tpu/utils/profiling.SPANS`) say of one benchmark run, part by part.

    python3 runs/host_span_table.py <trace dir or .xplane.pb> [out.json]
    python3 runs/host_span_table.py --run <cell> --seed N [--seconds S] [out.json]

First form, after a traced run (`benchmark/run.py ... --trace 1` leaves its
trace under `.benchmark_work/trace/<cell>`): per span name the count, wall and
CPU ms per dispatch (`cpu_us`, stamped at close since PR 42) and both as SELF
time (the span less its children on the same thread), the collections of
`r2d2.host.gc` by generation with the span each fell under, and the longest
dispatches with what they were made of. Wall less CPU is time the dispatch
thread was not running.

Second form, an UNTRACED run of the cell through the benchmark's own harness in
this process (a chip only), with every collection of 1 ms or more noted on the
host clock and the spans' always-on aggregates (`profiling.counters()`) sampled
once a second from a second thread: prints the result line's `notes.stall_s`
beside the collections that fell inside the measured window, which is how "the
pauses are the collector's" is told from "the pauses are the machine's", and
wall and CPU ms per dispatch of every span over the window's inner seconds. On
a host whose thread CPU clock ticks coarsely (the chip machine's: 10 ms) CPU
time is a count of ticks, and 30 s of dispatches hold six times a traced
window's. Reads; changes nothing.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time

_T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DISPATCH = "r2d2.dispatch"
LONG_GC_MS = 1.0


def table(spans) -> dict:
    """`spans`: benchmark.readers.host_span.Span, sorted by (start, -dur)."""
    n = sum(1 for s in spans if s.name == DISPATCH) or 1
    cpu = lambda s: 1e3 * float(s.stats.get("cpu_us", 0.0))  # ns
    rows, gcs, per_dispatch = {}, [], []
    for line in sorted({s.line for s in spans}):
        stack = []  # open spans of this thread: [span, children's wall ns, children's cpu ns]

        def close(upto):
            while stack and stack[-1][0].end <= upto:
                s, kids_wall, kids_cpu = stack.pop()
                r = rows.setdefault(s.name, {"n": 0, "wall": 0.0, "cpu": 0.0, "self_wall": 0.0, "self_cpu": 0.0})
                r["n"] += 1
                r["wall"] += s.dur
                r["cpu"] += cpu(s)
                r["self_wall"] += s.dur - kids_wall
                r["self_cpu"] += cpu(s) - kids_cpu
                if stack:
                    stack[-1][1] += s.dur
                    stack[-1][2] += cpu(s)
                if s.name == DISPATCH:
                    per_dispatch.append((s, s.dur - kids_wall))

        for s in spans:
            if s.line != line:
                continue
            close(s.start)
            if s.name == "r2d2.host.gc":
                gcs.append((s, stack[-1][0].name if stack else None))
            stack.append([s, 0.0, 0.0])
        close(float("inf"))
    ms = lambda ns: round(ns / n / 1e6, 7)
    out = {"dispatches": n, "per_dispatch_ms": {
        name: {"n": r["n"], "wall": ms(r["wall"]), "cpu": ms(r["cpu"]), "off_cpu": ms(r["wall"] - r["cpu"]),
               "self_wall": ms(r["self_wall"]), "self_cpu": ms(r["self_cpu"])}
        for name, r in sorted(rows.items(), key=lambda kv: -kv[1]["wall"])}}
    t0 = min((s.start for s in spans), default=0.0)
    by_gen = {}
    for s, under in gcs:
        g = by_gen.setdefault(int(s.stats.get("generation", -1)), {"n": 0, "ms": 0.0, "max_ms": 0.0, "collected": 0})
        g["n"] += 1
        g["ms"] = round(g["ms"] + s.dur / 1e6, 4)
        g["max_ms"] = round(max(g["max_ms"], s.dur / 1e6), 4)
        g["collected"] += int(s.stats.get("collected", 0))
    out["gc"] = {"by_generation": by_gen, "per_dispatch": round(len(gcs) / n, 3),
                 "long": [{"at_s": round((s.start - t0) / 1e9, 4), "ms": round(s.dur / 1e6, 3),
                           "cpu_ms": round(cpu(s) / 1e6, 3), "generation": int(s.stats.get("generation", -1)),
                           "under": under} for s, under in gcs if s.dur >= LONG_GC_MS * 1e6]}
    # the longest dispatches by host time outside the readback wait, and their parts
    def parts(d):
        inside = [s for s in spans if s.line == d.line and s is not d and d.start <= s.start and s.end <= d.end]
        return {"dispatch": int(d.stats.get("dispatch", -1)), "collect": int(d.stats.get("collect", -1)),
                "at_s": round((d.start - t0) / 1e9, 4), "wall_ms": round(d.dur / 1e6, 3),
                "cpu_ms": round(cpu(d) / 1e6, 3),
                "parts_ms": {s.name + ("" if not i else f"#{i}"): round(s.dur / 1e6, 3) for i, s in
                             enumerate(sorted(inside, key=lambda s: -s.dur)[:8])}}
    busy = lambda d: d.dur - sum(s.dur for s in spans if s.name == "r2d2.dispatch.readback" and s.line == d.line
                                 and d.start <= s.start and s.end <= d.end)
    ds = [s for s in spans if s.name == DISPATCH]
    out["longest_by_host_busy"] = [dict(parts(d), host_busy_ms=round(busy(d) / 1e6, 3))
                                   for d in sorted(ds, key=busy, reverse=True)[:4]]
    out["longest_by_self_time"] = [dict(parts(d), self_ms=round(self_ns / 1e6, 3))
                                   for d, self_ns in sorted(per_dispatch, key=lambda p: -p[1])[:4]]
    return out


def from_trace(path: str) -> dict:
    from benchmark import trace as tr
    from benchmark.readers import host_span

    path = path if path.endswith(".pb") else tr.find_xplane(path)
    return dict(table(host_span.load_spans(path, tr.load_patterns()["host_plane"])), file=path)


def untraced_run(cell: str, seed: int, seconds: float) -> dict:
    os.environ["JAX_PLATFORMS"] = "tpu"  # never a CPU fallback, as benchmark/run.py
    from benchmark.harness import run_cell

    seen, opened = [], []

    def note(phase, info):
        if phase == "start":
            opened.append(time.perf_counter())
        elif opened:
            t = opened.pop()
            if time.perf_counter() - t >= LONG_GC_MS / 1e3:
                seen.append((t - _T_START, time.perf_counter() - t, info["generation"]))

    from r2d2_tpu.utils import profiling

    snaps, done = [], threading.Event()

    def sample():
        while not done.wait(1.0):
            snaps.append((time.perf_counter() - _T_START, profiling.counters()))

    gc.callbacks.append(note)
    sampler = threading.Thread(target=sample, daemon=True)
    sampler.start()
    try:
        result = run_cell(ROOT, cell, seed, seconds, False, t_start=_T_START, require_tpu=True)
    finally:
        gc.callbacks.remove(note)
        done.set()
        sampler.join()
    notes = result.get("notes", {})
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    t0 = metrics["setup_s"] + float(notes.get("start_capture_s", 0.0))
    t1 = t0 + float(notes.get("window_s", seconds))
    inside = [{"at_s": round(t - t0, 3), "ms": round(1e3 * d, 2), "generation": g} for t, d, g in seen if t0 - 1.0 <= t <= t1 + 1.0]
    inner = [c for t, c in snaps if t0 + 0.5 <= t <= t1 - 0.5]
    spans_ms = {}
    if len(inner) >= 2:
        grown = lambda key: inner[-1].get(key, 0) - inner[0].get(key, 0)
        n = grown(DISPATCH + ".count") or 1
        spans_ms = {"dispatches": n, "snapshots": len(inner)}
        for key in sorted(inner[-1]):
            if key.endswith(".total_ns") and grown(key):
                name = key[: -len(".total_ns")]
                spans_ms[name] = {"n": grown(name + ".count"), "wall": round(grown(key) / n / 1e6, 4),
                                  "cpu": round(grown(name + ".cpu_ns") / n / 1e6, 4)}
    return {"cell": cell, "aggregates_per_dispatch_ms": spans_ms, "seed": seed, "correct": result["correct"], "metrics": metrics,
            "stall_s": notes.get("stall_s"), "period_s_median": notes.get("period_s_median"),
            "period_s_max": notes.get("period_s_max"), "window_s": notes.get("window_s"),
            "collections_of_1ms_or_more": {"whole_process": len(seen), "around_the_window": inside,
                                           "window_total_ms": round(sum(c["ms"] for c in inside), 2)},
            "gc_aggregate_whole_process": {k: v for k, v in profiling.counters().items() if k.startswith("r2d2.host.gc")}}


def main(argv) -> int:
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    if argv and argv[0] == "--run":
        import argparse

        p = argparse.ArgumentParser()
        p.add_argument("--run", required=True)
        p.add_argument("--seed", type=int, default=0)
        p.add_argument("--seconds", type=float, default=30.0)
        p.add_argument("out", nargs="?")
        a = p.parse_args(argv)
        out, dest = untraced_run(a.run, a.seed, a.seconds), a.out
    else:
        out, dest = from_trace(argv[0]), (argv[1] if len(argv) > 1 else None)
    text = json.dumps(out, indent=1)
    print(text)
    if dest:
        os.makedirs(os.path.dirname(os.path.abspath(dest)), exist_ok=True)
        with open(dest, "w") as fh:
            fh.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
