"""Reader `lstm_roofline`: the Pallas LSTM kernels' share of their roofline,
in percent: the least time the chip could take for one update's kernel calls
(2 forward + 1 sequence backward at the per-device batch; the larger of
FLOPs / peak FLOP/s and bytes / peak bytes/s for each, from flops.py) over
their measured device time per update. The bound that binds is printed."""

import jax
import jax.numpy as jnp

from benchmark import flops
from benchmark import trace as tr
from benchmark.readers import pattern_of


def read(spec, ctx):
    cfg = ctx.cfg
    updates = ctx.counters.get("updates")
    if ctx.trace_data is None or cfg is None or not updates or cfg.recurrent_core != "lstm":
        return None
    measured = tr.category_seconds(ctx.trace_data, pattern_of(spec, ctx))
    if measured <= 0:
        return None
    peaks = flops.device_peaks(jax.devices()[0].device_kind)
    rows = cfg.batch_size // max(cfg.dp_size, 1)
    least, bound = flops.lstm_kernels_roofline_seconds_per_update(
        cfg.seq_len, rows, cfg.hidden_dim, jnp.dtype(cfg.resolved_compute_dtype).itemsize, peaks)
    print(f"[bench] lstm kernels: roofline {least * 1e3:.3f} ms/update ({bound}-bound), "
          f"measured {measured / updates * 1e3:.3f} ms/update", flush=True)
    return 100.0 * least / (measured / updates)
