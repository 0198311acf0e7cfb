"""Reader `trace_gap`: median device-idle ms between consecutive executions
of the step programs (module line; spec "category" or "pattern")."""

import statistics

from benchmark import trace as tr
from benchmark.readers import pattern_of


def read(spec, ctx):
    if ctx.trace_data is None:
        return None
    gaps = tr.module_gaps_ms(ctx.trace_data, pattern_of(spec, ctx))
    return statistics.median(gaps) if gaps else None
