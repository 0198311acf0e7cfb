"""Read a trace by hand: which planes and lines exist, how events are named.

    python3 benchmark/trace_dump.py <trace dir or .xplane.pb> [out.json]

Prints (and optionally writes) every plane with its lines and event counts,
the stat keys seen on device events, and the device events' distinct texts by
self time, as trace.py's patterns see them. Look here before writing a pattern
(on-chip-measurement guide, section 6); never imports the program.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv) -> int:
    sys.path.insert(0, ROOT)
    from jax.profiler import ProfileData

    from benchmark import trace as tr

    path = argv[0] if argv[0].endswith(".pb") else tr.find_xplane(argv[0])
    pats = tr.load_patterns()
    planes = []
    stat_keys = {}
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"line": line.name, "events": len(evs),
                          "first_names": sorted({e.name for e in evs[:2000]})[:12]})
            if plane.name.startswith("/device:") and evs:
                for e in evs[:200]:
                    for k, v in e.stats:
                        stat_keys.setdefault(f"{line.name}:{k}", str(v)[:160])
        planes.append({"plane": plane.name, "lines": lines})
    t = tr.load(path, pats)
    out = {
        "file": path, "bytes": os.path.getsize(path), "planes": planes, "stat_keys": stat_keys,
        "modules": {d: sorted({e.name for e in evs})[:20] for d, evs in t.modules.items()},
        "names_by_self_time": tr.summarize_names(t, 80),
        "category_seconds": {k: tr.category_seconds(t, v) for k, v in pats["categories"].items()} if t.ops else {},
        "busy": tr.busy_seconds(t) if t.ops else None,
        "top_ops": tr.top_ops(t, pats["container"]), "idle_gaps": tr.idle_gaps_by_host_span(t),
    }
    text = json.dumps(out, indent=1)
    print(text[:6000])
    if len(argv) > 1:
        os.makedirs(os.path.dirname(os.path.abspath(argv[1])), exist_ok=True)
        with open(argv[1], "w") as fh:
            fh.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
