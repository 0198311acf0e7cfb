"""One small reader per kind of per-layer metric: `read(spec, ctx)` returns the
value, or None when there is nothing to read (no trace, no counter; the metric
is then left out of the line). A trace in which no event matches a pattern is
something to read: the time is 0, and the check of a later PR that removes
such an operation must still find the metric in the line.
`spec` is the metric's benchmark/layers/<metric>.json."""


def scaled(spec, ctx, seconds):
    """`seconds` x spec["scale"] / the counter spec["per"] names (if any);
    None when the divisor is missing. Zero seconds give 0.0."""
    per = ctx.counters.get(spec["per"]) if spec.get("per") else 1.0
    if not per:
        return None
    return max(seconds, 0.0) * float(spec.get("scale", 1.0)) / float(per)


def pattern_of(spec, ctx):
    return spec.get("pattern") or ctx.patterns["categories"][spec["category"]]
