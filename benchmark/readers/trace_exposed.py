"""Reader `trace_exposed`: collective time during which nothing else ran on
that device, mean over devices, per spec["per"], times spec["scale"]. Nothing
to read (None) on a single device; 0.0 where no collective ran or every one
was hidden behind other work."""

from benchmark import trace as tr
from benchmark.readers import pattern_of, scaled


def read(spec, ctx):
    if ctx.trace_data is None or len(ctx.trace_data.ops) < 2:
        return None
    seconds = tr.exposed_seconds(ctx.trace_data, pattern_of(spec, ctx), ctx.patterns["container"])
    return scaled(spec, ctx, seconds)
