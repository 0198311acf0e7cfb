"""Mesh construction and sharding rules.

Axes:
  dp — data parallel: the learner batch splits across this axis; gradient
       all-reduce (psum) is inserted by XLA because params are replicated.
  tp — tensor parallel: the LSTM's wide kernels shard their 4H axis over
       tp via the GSPMD annotations from `train_state_shardings` below.
       Plain-jit planes (host/device replay) partition directly from the
       shardings; the "sharded" shard_map plane composes dp×tp because
       its maps are manual over dp ONLY (axis_names={"dp"}) with tp left
       GSPMD-auto. The multihost plane pins tp=1 (config.validate).

Batches shard their leading (batch) dimension over dp; everything else is
replicated. With params replicated and batch sharded, jit emits a psum over
dp for the gradients — data parallelism without hand-written collectives.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    dp: Optional[int] = None,
    tp: int = 1,
    devices: Optional[Sequence] = None,
    fsdp: int = 1,
) -> Mesh:
    """(dp, tp) mesh, growing a third "fsdp" axis when fsdp > 1.

    The fsdp axis shards optimizer-state moments (parallel/sharding_map
    spec rules); with fsdp == 1 the mesh keeps its historical two-axis
    shape so every existing P("dp")/P("tp") spec and shard_map
    axis_names={"dp"} plane is untouched."""
    devices = list(devices if devices is not None else jax.devices())
    if fsdp < 1:
        raise ValueError(f"fsdp must be >= 1, got {fsdp}")
    if dp is None:
        dp = len(devices) // (tp * fsdp)
    if dp * tp * fsdp != len(devices):
        raise ValueError(
            f"dp*tp*fsdp = {dp * tp * fsdp} != {len(devices)} devices"
        )
    if fsdp == 1:
        return Mesh(np.asarray(devices).reshape(dp, tp), axis_names=("dp", "tp"))
    return Mesh(
        np.asarray(devices).reshape(dp, tp, fsdp),
        axis_names=("dp", "tp", "fsdp"),
    )


def dp_manual_axes(mesh: Mesh):
    """`axis_names` for the dp-sharded planes' shard_maps (the sharded and
    multihost train steps and megasteps). While another mesh axis has
    size > 1 the map is manual over dp ONLY, so GSPMD keeps partitioning
    tp-sharded kernels inside each dp shard. When every other axis has
    size 1 there is nothing left for GSPMD to partition and the map is
    made FULLY manual (None) — which the TPU demands: Mosaic refuses a
    pallas_call under any auto axis, even a size-1 one ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map" — the first --dp 4 run on real chips). config.
    resolved_core_backend picks the Pallas core exactly in that case."""
    others = [a for a in mesh.axis_names if a != "dp" and mesh.shape[a] > 1]
    return {"dp"} if others else None


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading axis over dp, rest replicated."""
    return NamedSharding(mesh, P("dp"))


def manual_data_axes(mesh: Mesh) -> tuple:
    """Mesh axes the manual-partition train step shards the BATCH over:
    dp always, fsdp too when the mesh carries it. Splitting the batch
    over fsdp is what promotes the axis from ZeRO-1 to ZeRO-2 — each
    fsdp member computes gradients for a DISTINCT batch slice, so the
    gradient reduce-scatter onto the moment shards is a true reduction
    (scattering replicated gradients would multiply them by fsdp)."""
    return ("dp", "fsdp") if "fsdp" in mesh.axis_names else ("dp",)


def manual_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch sharding for the manual-partition train step: leading axis
    over (dp, fsdp) — see manual_data_axes."""
    return NamedSharding(mesh, P(manual_data_axes(mesh)))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def slab_sharding(mesh: Mesh) -> NamedSharding:
    """Replay-slab sharding: the block axis splits over dp, everything
    else replicated — the spec every dp-sharded replay store uses
    (sharded_store's flat stores, the reshard scatter's device_put)."""
    return NamedSharding(mesh, P("dp"))


def slab_partition_map(mesh: Mesh, num_blocks: int, axis: str = "dp"):
    """The per-slab partition map that extends slab_sharding with explicit
    block ownership: shard i on `axis` owns global block rows
    [start, end). This is what snapshot topology manifests record and the
    reshard-on-resume path (replay/reshard.py) re-splits against — the
    NamedSharding alone says "split over dp", the map says exactly which
    logical blocks each shard holds."""
    n = int(mesh.shape[axis])
    if num_blocks % n != 0:
        raise ValueError(f"num_blocks {num_blocks} not divisible by {axis}={n}")
    bps = num_blocks // n
    return {i: (i * bps, (i + 1) * bps) for i in range(n)}


def shard_batch(mesh: Mesh, batch_pytree):
    """device_put every leaf with its batch dim sharded over dp."""
    sh = batch_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sh), batch_pytree)


def train_state_shardings(state, mesh: Mesh, rules=None):
    """Per-leaf NamedShardings for a TrainState — now data-driven.

    The Megatron column/row layout that used to be hardcoded here as name
    sets lives in parallel/sharding_map.DEFAULT_RULES, an ordered table of
    wildcard param-name patterns -> mesh-axis tuples, which also carries
    the fsdp rule for optimizer-state moments and the serve plane's int8
    placement. This wrapper keeps the historical import site/signature;
    see sharding_map.py for the pattern grammar, the per-layer rationale,
    and the tp/fsdp axis semantics.

    Scope is unchanged: everywhere except multihost. Plain-jit planes
    partition from these annotations alone; the "sharded" shard_map
    planes are manual over dp only (axis_names={"dp"}) with tp GSPMD-auto
    (dp×tp parity pinned by tests/test_sharded_replay.py /
    test_sharded_megastep.py); multihost keeps params replicated per its
    P() in_specs. Adam's mu/nu mirror the param tree structure, so the
    same wildcard rules shard them consistently."""
    from r2d2_tpu.parallel.sharding_map import train_state_shardings as _tss

    return _tss(state, mesh, rules)


def tp_probe_kernel(params):
    """The leaf to assert tp-sharding on, independent of recurrent core.

    With an LSTM core this is the gate kernel `core/wi` — the docstring
    above calls it the hard case (the scan's per-step h re-gather), so
    when it exists the checks keep probing it. The LRU core deliberately
    carries none of the Megatron-annotated names (models/lru.py), so
    there the probe falls back to the encoder's `Dense_0` kernel, which
    is COLUMN-parallel under every encoder and every core."""
    p = params["params"]
    core = p.get("core", {})
    if "wi" in core:
        return core["wi"]
    return p["enc"]["Dense_0"]["kernel"]
