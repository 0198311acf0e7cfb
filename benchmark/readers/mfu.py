"""Reader `mfu`: model FLOP/s utilization of the learner, in percent:
flops.update_flops (what the algorithm requires; recompute not counted) x
updates/s / (chips x the device_kind's published bf16 peak)."""

import jax

from benchmark import flops


def read(spec, ctx):
    rate = ctx.counters.get("updates_per_s")
    cfg = ctx.cfg
    if not rate or cfg is None:
        return None
    peaks = flops.device_peaks(jax.devices()[0].device_kind)
    per_update = flops.update_flops(
        encoder=cfg.encoder, obs_shape=cfg.obs_shape, hidden=cfg.hidden_dim,
        action_dim=cfg.action_dim, core=cfg.recurrent_core, lru_chunk=cfg.lru_chunk,
        batch=cfg.batch_size, burn_in=cfg.burn_in_steps, learning=cfg.learning_steps,
        forward=cfg.forward_steps,
    )
    chips = ctx.cell.workload["chips"]
    return 100.0 * per_update * rate / (chips * peaks["bf16_flops_per_s"])
