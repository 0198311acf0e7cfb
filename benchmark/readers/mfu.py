"""Reader `mfu`: model FLOP/s utilization of the learner, in percent: what
the algorithm requires per update (the `update_flops(cfg)` of the reference
module the configuration names; recompute not counted) x updates/s /
(chips x the device_kind's published bf16 peak)."""

import jax

from benchmark import flops, harness


def read(spec, ctx):
    rate = ctx.counters.get("updates_per_s")
    if not rate or ctx.cfg is None:
        return None
    peaks = flops.device_peaks(jax.devices()[0].device_kind)
    per_update = harness.reference_for(ctx.cell).update_flops(ctx.cfg)
    chips = ctx.cell.workload["chips"]
    return 100.0 * per_update * rate / (chips * peaks["bf16_flops_per_s"])
