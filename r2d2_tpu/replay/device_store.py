"""Device-resident replay data plane.

Motivation: shipping each (64, 85, 84, 84) uint8 batch from host RAM is a
~38 MB host->device copy serialized ahead of an update that touches far
fewer bytes once the data is resident (how the two compare on a directly
attached chip is not measured yet, ROADMAP S4). The reference
pays this by construction — its replay is host memory and every batch rides
a pickle queue (reference worker.py:157,385-389).

TPU-native split instead:

- control plane stays on HOST (replay/control_plane.py, shared with the
  host-data-plane buffer): sum tree, block pointer, stale-priority window
  masking, size accounting — byte-addressed, branchy, cheap.
- data plane lives in HBM: obs / last_action / last_reward / action /
  n_step_reward / gamma / hidden / per-sequence counters, one preallocated
  device array per field, written once per block (a ~3 MB upload amortized
  over block_length env steps) via a donated jitted dynamic-slice update.
- a training update ships ONLY the sampled sequence coordinates
  (b, s, is_weights — about a kilobyte); the fused train step gathers the
  windows in-jit straight out of HBM (learner.make_fused_multi_train_step).

Concurrency contract: `_write` DONATES the store buffers, so a stores
reference obtained before an add_block is dead after it. Dispatch every
consumer through `run_with_stores(fn)` — it holds the buffer lock across
the dispatch, serializing against add_block's swap. Never cache
`self.stores` across calls.

Store layout: every field is (num_blocks, *store_field_specs(cfg)[field]).
Obs is NOT (slot, *obs_shape): each frame is kept as lane-aligned rows,
(slot, R, 128) with R = ceil(frame bytes / 128) and a zero tail
(replay/block.frames_to_rows; an 84x84x1 frame is 56 rows, 1.6 % padding),
its bytes in the encoder's block order (cfg.resolved_frame_block).
The TPU runtime then lays the store out row-major, a frame is R contiguous
tiles, and the in-jit gather and the donated slab write work on the store in
place. With raw frames the runtime put the BLOCK index on the lanes and both
step programs re-laid the whole store out on every dispatch (PERF.md
finding 1, repaired in PR 25). pad_block_fields and the collector (from its scan
body on) write rows, learner.make_store_gather hands frames back (canonical,
or as stored to the step programs); blocks, the host buffer, the
disk tier and snapshot files keep frames.

Capacity note: obs dominates HBM use at 7,168 bytes per stored step for
84x84 (block_slot_len steps per block_length transitions: 7.9 KB per
transition at block 400); a 16 GB chip holds the store plus what the step
programs need beside it (the benchmark runs 512,000 transitions = 4.05 GB;
what the compiler admits above that: PERF.md finding 25.1), so configure
buffer_capacity to budget. Scaling to the full reference capacity shards the
block dimension over the mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from r2d2_tpu.config import R2D2Config
from r2d2_tpu.replay.block import Block, frames_to_rows, store_field_specs
from r2d2_tpu.replay.control_plane import ReplayControlPlane


@dataclasses.dataclass
class SampleIdx:
    """Host-side sample coordinates; everything else stays in HBM."""

    b: np.ndarray           # (B,) block slot
    s: np.ndarray           # (B,) sequence-in-block
    is_weights: np.ndarray  # (B,) float32
    idxes: np.ndarray       # (B,) global sequence slots (priority updates)
    old_ptr: int
    env_steps: int
    # draw-time ptr_advances stamp (lap detection); None = no lap check,
    # matching the update_priorities contract
    old_advances: Optional[int] = None


class DeviceReplayBuffer(ReplayControlPlane):
    def __init__(self, cfg: R2D2Config):
        super().__init__(cfg)
        nb = cfg.num_blocks
        if cfg.priority_plane == "device":
            from r2d2_tpu.replay.device_sum_tree import DeviceSumTree

            # HBM float32 twin of the host tree: ingestion/retirement keep
            # it in sync via _tree_write; sampling + priority write-back
            # run in-jit inside the learner superstep (superstep_run)
            self.attach_device_tree(
                DeviceSumTree(cfg.num_sequences, cfg.prio_exponent, cfg.is_exponent)
            )
        self.stores: Dict[str, jnp.ndarray] = {
            k: jnp.zeros((nb, *shape), dt)
            for k, (shape, dt) in store_field_specs(cfg).items()
        }

        # donated slot write: XLA updates the big arrays in place
        def _write(stores, ptr, vals):
            out = {}
            for k, arr in stores.items():
                out[k] = jax.lax.dynamic_update_index_in_dim(arr, vals[k], ptr, axis=0)
            return out

        self._write = jax.jit(_write, donate_argnums=(0,))

        # batched slab write for the on-device collector: E CONTIGUOUS
        # slots land in one donated dispatch (vals stay in HBM end to end).
        # Contiguity is load-bearing: a dynamic_update_slice writes E slabs
        # at memcpy speed, where a dynamic-index scatter over the multi-GB
        # store costs seconds on TPU (measured 2.2s vs 0.03s at E=256) —
        # the ring pointer wraps early (_reserve_contiguous) to guarantee it
        def _write_slab(stores, start, vals):
            return {
                k: jax.lax.dynamic_update_slice_in_dim(arr, vals[k], start, axis=0)
                for k, arr in stores.items()
            }

        self._write_slab = jax.jit(_write_slab, donate_argnums=(0,))

    # ------------------------------------------------------------------ add

    @staticmethod
    def pad_block_fields(cfg: R2D2Config, block: Block) -> Dict[str, np.ndarray]:
        """Pad every block field to its fixed store-slot shape on host
        (cheap memset) — shared with the dp-sharded store."""
        S, slot, bl = cfg.seqs_per_block, cfg.block_slot_len, cfg.block_length

        def pad(a, length, dtype):
            out = np.zeros((length, *a.shape[1:]), dtype)
            out[: len(a)] = a
            return out

        out = {
            "obs": frames_to_rows(
                pad(block.obs, slot, np.uint8), cfg.obs_shape, cfg.resolved_frame_block
            ),
            "last_action": pad(block.last_action.astype(np.int32), slot, np.int32),
            "last_reward": pad(block.last_reward, slot, np.float32),
            "action": pad(block.action.astype(np.int32), bl, np.int32),
            "n_step_reward": pad(block.n_step_reward, bl, np.float32),
            "gamma": pad(block.gamma, bl, np.float32),
            # store dtype (f32 | bf16) — the donated jitted writes require
            # vals to match store_field_specs exactly; the analysis plane's
            # check_store_field_dtypes (jaxpr_rules) pins the agreement in
            # tier-1, so a drift here fails the gate before it hits _write
            "hidden": pad(block.hidden, S, cfg.state_dtype),
            "burn_in": pad(block.burn_in_steps, S, np.int32),
            "learning": pad(block.learning_steps, S, np.int32),
            "forward": pad(block.forward_steps, S, np.int32),
        }
        if cfg.num_tasks > 1:
            # scalar block task broadcast per sequence (store_field_specs'
            # multi-task-only field — same gate, same dtype contract)
            out["task"] = np.full((S,), block.task, np.int32)
        return out

    def add_block(
        self, block: Block, priorities: np.ndarray, episode_reward: Optional[float]
    ) -> None:
        vals = self.pad_block_fields(self.cfg, block)

        with self.lock:
            # write first, account last (see replay_buffer.add_block): the
            # fallible work — shape validation in pad_block_fields and the
            # jitted write dispatch — completes before tree/ptr mutate
            self.stores = self._write(self.stores, self.block_ptr, vals)
            self._account_add(
                block.num_sequences, int(block.learning_steps.sum()), priorities, episode_reward
            )

    def add_blocks_batch(
        self,
        fields: Dict[str, jnp.ndarray],
        num_seq: np.ndarray,
        learning_totals: np.ndarray,
        priorities: np.ndarray,
        episode_rewards: np.ndarray,
        dones: np.ndarray,
    ) -> None:
        """Write E collector-packed blocks in one scatter (collect.py).

        fields: dict of (E, slot, ...) DEVICE arrays keyed like
        self.stores — they never visit host memory. num_seq /
        learning_totals / priorities (E, seqs_per_block) / episode_rewards
        / dones are small host arrays for sum-tree + stats accounting.
        episode_rewards[i] counts only when dones[i] (a truncated chunk is
        not a finished episode)."""
        E = len(num_seq)
        nb = self.cfg.num_blocks
        if E > nb:
            raise ValueError(f"{E} blocks per batch exceeds store of {nb} slots")
        with self.lock:
            start = self._reserve_contiguous(E)
            self.stores = self._write_slab(
                self.stores, jnp.int32(start), fields
            )
            self._account_blocks(
                num_seq, learning_totals, priorities, episode_rewards, dones
            )

    # --------------------------------------------------------------- sample

    def _draw_sample_idx(self, rng: np.random.Generator) -> SampleIdx:
        """One tree draw packaged as SampleIdx. Caller holds self.lock."""
        b, s, idxes, is_weights = self._draw(rng)
        return SampleIdx(
            b=b.astype(np.int32),
            s=s.astype(np.int32),
            is_weights=is_weights,
            idxes=idxes,
            old_ptr=self.block_ptr,
            env_steps=self.env_steps,
            old_advances=self.ptr_advances,
        )

    def sample_indices(self, rng: np.random.Generator) -> SampleIdx:
        """Tree draw only — the kilobyte that crosses the wire per update."""
        with self.lock:
            return self._draw_sample_idx(rng)

    def sample_and_run(self, rng: np.random.Generator, k: int, fn: Callable):
        """Draw k coordinate sets and dispatch fn(stores, draws) under ONE
        lock hold (multi-update path, learner.make_fused_multi_train_step).

        Safety: the lock orders this dispatch before any later add_block's
        donated write; the device stream executes in dispatch order, so the
        in-jit gathers read exactly the data the coordinates were drawn
        against — an add can never retarget a sampled slot in between."""
        with self.lock:
            draws = [self._draw_sample_idx(rng) for _ in range(k)]
            return draws, fn(self.stores, draws)

    def superstep_keys(self, key: jax.Array) -> jax.Array:
        """The superstep's key from one dispatch key: one tree, one stream
        (ShardedDeviceReplay makes one per dp shard of it)."""
        return key

    def superstep_run(self, fn: Callable):
        """Dispatch an in-jit sample/train/write-back superstep under ONE
        lock hold (priority_plane="device"): fn(stores, tree,
        num_seq_store) -> (tree_out, rest). The output tree is installed
        before the lock releases, so every later _tree_write enqueues its
        device update AFTER the superstep in stream order — the device
        tree serializes exactly like the host tree does under the lock,
        and ingestion racing the dispatch wins over the dispatch's
        write-backs for the slots it overwrites (the same verdict the
        host pointer-window mask reaches). Returns `rest`."""
        with self.lock:
            tree_out, rest = fn(
                self.stores, self.dtree.tree, jnp.asarray(self.num_seq_store)
            )
            self.dtree.swap(tree_out)
            return rest

    # ------------------------------------------------------------- dispatch

    def run_with_stores(self, fn: Callable):
        """Run fn(stores) under the buffer lock.

        Required for every consumer of the HBM stores: add_block's donated
        write invalidates the previous buffers, so reads must serialize
        against the swap. fn should only DISPATCH device work (fast), not
        block on results."""
        with self.lock:
            return fn(self.stores)
