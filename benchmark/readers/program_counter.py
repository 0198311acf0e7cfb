"""Reader `program_counter`: a number the PROGRAM counted where the work
happens (r2d2_tpu/utils/profiling.counters(): plain counts and the always-on
aggregates `<span>.count|.total_ns` of every host span), since the
process started. spec: {"key": <counter>, "scale": 1.0}. Nothing to read
(None): no trace, or a program without the facility (a parent commit). A
counter that is absent reads 0.0 and says so."""


def read(spec, ctx):
    if ctx.trace_data is None or not ctx.trace_data.ops:
        return None
    try:
        from r2d2_tpu.utils import profiling

        counters = profiling.counters()
    except (ImportError, AttributeError):
        return None
    value = counters.get(spec["key"])
    if value is None:
        print(f"[bench] {spec.get('name', '?')}: the program has not counted {spec['key']!r}", flush=True)
        return 0.0
    return float(value) * float(spec.get("scale", 1.0))
