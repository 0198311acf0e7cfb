"""Block — the unit of replay storage.

Mirrors the reference Block (reference worker.py:23-66) with two TPU-first
changes:

- `last_action` is stored as a scalar uint8 index, not a bool one-hot
  (reference worker.py:31,498). One-hot expansion happens on device inside
  the jitted step (jax.nn.one_hot) — an A-fold replay-RAM saving and less
  host->device traffic.
- Per-sequence step counters are int32, not uint8, so block/burn-in/learning
  spans > 255 (the long-context preset) don't silently wrap (SURVEY.md
  quirk 12).

Observations keep the reference's uint8 storage; normalization to [0, 1]
happens exactly once, on device (SURVEY.md quirk 15).

The DEVICE stores keep each frame as lane-aligned rows (`frames_to_rows`):
the frame's bytes, zero-padded to a multiple of 128, as (R, 128). The TPU
runtime lays a buffer out with whichever dimension pads least on the 128
lanes; with raw (84, 84, 1) frames that is the block index, one frame is
scattered a byte at a time over the whole store, and every step program
re-laid the whole store out before it could gather (PERF.md finding 1).
Within its rows a frame's bytes stand in the order the encoder's first conv
reads them: blocks of `cfg.resolved_frame_block` (models/encoders.frame_block;
4 for the Nature trunk on 84x84, where a frame is 21 x 21 blocks of 16 bytes
and the conv a 2x2/1 conv over 16 channels; 1 = the frame as it is). The step
programs hand the conv gathered rows by `rows_as_stored`: a slice and a
reshape over ONE merged frame axis, so that the chip re-tiles the batch once,
in uint8, between the gather and the conv.
`frames_to_rows` / `rows_to_frames` own that order: every writer and reader of
a device store goes through them with the config's block.
Blocks, the host ReplayBuffer, the disk tier and snapshot files keep frames.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from r2d2_tpu.models.core import state_spec
from r2d2_tpu.models.encoders import block_frames, blocked_shape, unblock_frames


@dataclasses.dataclass
class Block:
    # (stored_steps, *obs_shape) uint8; stored_steps = burn_in_steps[0] +
    # sum(learning_steps) + 1 (trailing seed entry for the next window)
    obs: np.ndarray
    # (stored_steps,) uint8 — action that *led to* the aligned obs
    last_action: np.ndarray
    # (stored_steps,) float32 — reward that came with the aligned obs
    last_reward: np.ndarray
    # (T,) uint8 — action taken at each learning step
    action: np.ndarray
    # (T,) float32 — n-step return R_t
    n_step_reward: np.ndarray
    # (T,) float32 — bootstrap discount gamma_n(t); 0 past a terminal
    gamma: np.ndarray
    # (num_sequences, *state_shape) — the core's stored state
    # (models/core.py) at the TRUE replay-window start of each sequence
    # (fixes SURVEY.md quirk 1). Packed float32 by the accumulator; the
    # stores downcast to cfg.state_dtype (bfloat16 under precision="bf16")
    # at write time.
    hidden: np.ndarray
    num_sequences: int
    # (num_sequences,) int32 each
    burn_in_steps: np.ndarray
    learning_steps: np.ndarray
    forward_steps: np.ndarray
    # multi-task plane: the task id the producing actor was collecting
    # (multitask/registry.py). Scalar per block — one actor serves one
    # task — broadcast per-sequence by the stores. 0 on single-task runs.
    task: int = 0

    @property
    def stored_steps(self) -> int:
        return len(self.obs)


LANES = 128  # the TPU's minor tile dimension


def obs_rows(obs_shape) -> int:
    """R: how many 128-byte rows hold one frame."""
    return -(-int(np.prod(obs_shape)) // LANES)


def frames_to_rows(frames, obs_shape, block: int = 1):
    """(..., *obs_shape) -> (..., R, 128): put each frame in `block` order
    (module docstring), flatten it, zero-pad its tail to R * 128 bytes. numpy
    in, numpy out; anything else goes through jax.numpy (traceable)."""
    obs_shape = tuple(obs_shape)
    lead = frames.shape[: frames.ndim - len(obs_shape)]
    if frames.shape[len(lead):] != obs_shape:
        raise ValueError(f"frames {frames.shape} do not end in obs_shape {obs_shape}")
    n, R = int(np.prod(obs_shape)), obs_rows(obs_shape)
    flat = block_frames(frames, obs_shape, block).reshape(*lead, n)
    if R * LANES != n:
        if isinstance(frames, np.ndarray):
            pad = np.pad
        else:
            import jax.numpy as jnp

            pad = jnp.pad
        flat = pad(flat, [(0, 0)] * len(lead) + [(0, R * LANES - n)])
    return flat.reshape(*lead, R, LANES)


def rows_as_stored(rows, obs_shape, block: int = 1):
    """(..., R, 128) -> (..., *blocked_shape): the frames in the order the
    rows keep them, by a slice and a reshape alone.

    The rows become bytes with every leading axis MERGED, and only the result
    has `lead` again: flattened under (B, T), the chip's compiler tiled the
    batch's bytes over (T, bytes), 32 frames of one row to a tile, and the
    model's own merge of (B, T) before the first conv then cost three more
    passes over the batch, two of them at bf16's two bytes. Merged here, the
    frame index is one axis from the store gather to the conv and the batch is
    re-tiled once, in uint8 (PERF.md finding 43). The values are the same for
    every `lead`, `()` and `(N,)` included."""
    obs_shape = tuple(obs_shape)
    n, R = int(np.prod(obs_shape)), obs_rows(obs_shape)
    if rows.shape[-2:] != (R, LANES):
        raise ValueError(f"rows {rows.shape} do not end in {(R, LANES)}")
    lead = rows.shape[:-2]
    return rows.reshape(-1, R * LANES)[:, :n].reshape(*lead, *blocked_shape(obs_shape, block))


def rows_to_frames(rows, obs_shape, block: int = 1):
    """(..., R, 128) -> (..., *obs_shape): the inverse of frames_to_rows."""
    return unblock_frames(rows_as_stored(rows, obs_shape, block), tuple(obs_shape), block)


def store_field_specs(cfg):
    """Per-slot (shape, dtype) of every replay-store field, WITHOUT the
    leading block axis — the single source of truth shared by all device
    store planes (device_store / sharded_store / multihost_store) and, for
    the per-sequence fields, by the host stores (replay_buffer /
    tiered_store). Adding a Block field means extending this map and
    pad_block_fields once."""
    S, slot, bl = cfg.seqs_per_block, cfg.block_slot_len, cfg.block_length
    state_shape, state_dtype = state_spec(cfg)
    return {
        # frames as lane-aligned rows (module docstring, frames_to_rows)
        "obs": ((slot, obs_rows(cfg.obs_shape), LANES), np.uint8),
        "last_action": ((slot,), np.int32),
        "last_reward": ((slot,), np.float32),
        "action": ((bl,), np.int32),
        "n_step_reward": ((bl,), np.float32),
        "gamma": ((bl,), np.float32),
        # the core's own statement (models/core.py), at cfg.state_dtype:
        # float32 on the golden path, bfloat16 under precision="bf16"
        # (half the HBM/H2D bytes; the model cores cast back to their
        # compute dtype on use)
        "hidden": ((S, *state_shape), state_dtype),
        "burn_in": ((S,), np.int32),
        "learning": ((S,), np.int32),
        "forward": ((S,), np.int32),
    } | (
        # per-sequence task ids, present ONLY on multi-task configs so the
        # single-task store layout (and every golden-path jaxpr/donation
        # contract over it) is byte-identical to before
        {"task": ((S,), np.int32)} if cfg.num_tasks > 1 else {}
    )


# The per-step fields a demoted block carries in its disk-segment record,
# in record order (replay/disk_tier.py walks them to size and parse the
# fixed-geometry slots). The small per-sequence metadata (hidden carries,
# burn_in/learning/forward spans, task id) stays RAM-resident for disk
# slots — the control plane needs it to keep demoted sequences sampleable
# without touching the segment, and it is a rounding error next to the
# per-step planes the record actually holds.
DISK_FIELDS = (
    "obs", "last_action", "last_reward", "action", "n_step_reward", "gamma",
)


def disk_field_specs(cfg):
    """Per-slot (shape, dtype) of every disk-segment record field, in
    DISK_FIELDS order. Dtypes mirror the HOST slab (uint8 scalar actions,
    replay_buffer.py), not the device-store int32 layout above — the disk
    tier spills host rows and must round-trip them bit-exactly."""
    slot, bl = cfg.block_slot_len, cfg.block_length
    return {
        "obs": ((slot, *cfg.obs_shape), np.uint8),
        "last_action": ((slot,), np.uint8),
        "last_reward": ((slot,), np.float32),
        "action": ((bl,), np.uint8),
        "n_step_reward": ((bl,), np.float32),
        "gamma": ((bl,), np.float32),
    }
