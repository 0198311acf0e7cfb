"""Reader `trace_idle`: 100 x (1 - union of device op intervals / window),
the mean over the devices used."""

from benchmark import trace as tr


def read(spec, ctx):
    if ctx.trace_data is None or not ctx.trace_data.ops:
        return None
    _, _, idle = tr.busy_seconds(ctx.trace_data)
    return 100.0 * sum(idle) / len(idle)
