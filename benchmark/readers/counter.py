"""Reader `counter`: a number the driver counted (program counters, the
benchmark's own clocks). spec: {"key": <counter name>, "scale": 1.0}."""


def read(spec, ctx):
    v = ctx.counters.get(spec["key"])
    return None if v is None else float(v) * float(spec.get("scale", 1.0))
