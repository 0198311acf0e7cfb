"""Reader `trace_pattern`: summed self time of the device events matching a
pattern, mean over devices. spec: "category" (a name in trace_patterns.json)
or "pattern" (a regex of the metric's own); "per" (a counter to divide by,
e.g. updates); "scale" (1e3 = ms). "busy": true takes the union of ALL device
events instead of a pattern. A pattern that matches no event of the trace
reads 0.0, and says so on a progress line (a stale pattern looks the same as
an operation that is gone: look at the breakdown)."""

from benchmark import trace as tr
from benchmark.readers import pattern_of, scaled


def read(spec, ctx):
    if ctx.trace_data is None or not ctx.trace_data.ops:
        return None
    if spec.get("busy"):
        seconds = tr.busy_seconds(ctx.trace_data)[0]
    else:
        seconds = tr.category_seconds(ctx.trace_data, pattern_of(spec, ctx))
        if seconds <= 0.0:
            print(f"[bench] {spec.get('name', '?')}: no device event matches "
                  f"{pattern_of(spec, ctx)!r}", flush=True)
    return scaled(spec, ctx, seconds)
