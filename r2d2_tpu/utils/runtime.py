"""What a process actually runs on, said once at start-up.

Every selection this codebase makes from the jax backend — Pallas kernel
or lax.scan core, compiled or interpreted kernels — used to be made at
trace time and recorded nowhere, and stock jax
falls back to the CPU when the TPU does not initialise: a run could exit
0 on the scan reference on a CPU and look like a slow TPU run. The train,
serve and evaluate CLIs therefore print ONE line, in one format,

    [runtime] {"entry": "train", "platform": "tpu", "device_kind": ...}

and the trainer stamps the same fields into its first metrics record.
chip_smoke.py (and later the benchmark) parse that line and fail when it
does not say `tpu` with a compiled Pallas core.
"""

from __future__ import annotations

import json

from r2d2_tpu.config import R2D2Config


def describe_runtime(cfg: R2D2Config) -> dict:
    """Device and resolved-selection facts for `cfg` in this process."""
    import jax

    devices = jax.devices()
    core = cfg.resolved_core_backend
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "core": core,
        # the LRU's training recurrence: pallas (compiled kernel) | scan |
        # chunked; `core` itself stays "lru"
        **({"lru_recurrence": cfg.resolved_lru_recurrence} if core == "lru" else {}),
        # a Pallas core off-TPU runs under the interpreter (how the CPU
        # tests pin kernel parity) — never a device measurement
        "pallas_interpreted": core == "pallas" and jax.default_backend() != "tpu",
        # the block in which the encoder's first conv reads a frame, and so
        # the byte order of a frame in the device stores' rows
        # (replay/block.py): blocked | frames (block 1: frames as they are)
        "frame_block": cfg.resolved_frame_block,
        "store_order": "blocked" if cfg.resolved_frame_block > 1 else "frames",
        # how learner.make_store_gather reads a sampled sequence out of the
        # device stores: its frames by one clipped index each, its five
        # per-step scalar fields as one window of their row (PR 41; one
        # algorithm everywhere: a tree before it has no such key)
        "store_gather": "frames+windows",
    }


def describe_placement(**trees) -> dict:
    """Where the work sits on a multi-device run: for each named pytree
    the ids of the devices holding its addressable shards, plus every
    local device's `bytes_in_use` (None where the backend reports no
    memory stats, e.g. the CPU). The trainer prints it as one
    `[placement] {...}` line when it runs on a mesh, so "four devices"
    is checked from what the arrays say, not from the flag."""
    import jax

    out = {
        name: sorted({
            shard.device.id
            for leaf in jax.tree.leaves(tree)
            for shard in leaf.addressable_shards
        })
        for name, tree in trees.items()
    }
    out["bytes_in_use"] = {
        str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
        for d in jax.local_devices()
    }
    return out


def print_runtime_banner(entry: str, cfg: R2D2Config, **extra) -> dict:
    """Print the `[runtime] {...}` line for a CLI entry point; returns
    the dict (the trainer stamps it into its first metrics record)."""
    info = {"entry": entry, **describe_runtime(cfg), **extra}
    print("[runtime] " + json.dumps(info), flush=True)
    return info

