"""Shared host-side replay control plane.

Both replay buffers — host data plane (replay_buffer.ReplayBuffer) and HBM
data plane (device_store.DeviceReplayBuffer) — run the SAME control logic:
sum-tree priorities, circular block pointer, eviction/size accounting,
clamped stratified sampling of sequence coordinates, and the stale-priority
pointer-window rejection of reference worker.py:290-307. It lives here once
so a fix to any of the subtle parts (wrap-around masking, zero-leaf clamp)
cannot diverge between the two data planes.
"""

from __future__ import annotations

import threading
from typing import Optional, Tuple

import numpy as np

from r2d2_tpu.config import R2D2Config
from r2d2_tpu.replay.sum_tree import SumTree
from r2d2_tpu.utils.profiling import count


def shard_config(cfg: R2D2Config, dp: int) -> R2D2Config:
    """The per-shard (1/dp) view of a config, for dp-sharded replay planes:
    each shard's control plane sees its slice of capacity/batch and knows
    nothing of the mesh."""
    return cfg.replace(
        buffer_capacity=cfg.buffer_capacity // dp,
        learning_starts=max(cfg.learning_starts // dp, 1),
        batch_size=cfg.batch_size // dp,
        dp_size=1,
        tp_size=1,
        replay_plane="host",
        collector="host",  # collection is the PARENT plane's concern
        updates_per_dispatch=1,
        # the PARENT plane owns device-tree residency and the superstep;
        # each shard's control plane is plain host bookkeeping (its device
        # tree, when any, is attached by the parent)
        priority_plane="host",
        superstep_dispatches=1,
    )


class ReplayControlPlane:
    def __init__(self, cfg: R2D2Config, native: Optional[object] = None):
        self.cfg = cfg
        if native is None and cfg.use_native_replay:
            from r2d2_tpu._native import load_native

            native = load_native()  # None if the toolchain is unavailable
        self.native = native
        self.tree = SumTree(
            cfg.num_sequences, cfg.prio_exponent, cfg.is_exponent, native=native
        )
        self.block_ptr = 0
        # monotone count of ring-pointer advances (writes + retirement
        # jumps): lap detection for the staleness mask. The wrapped pointer
        # alone cannot distinguish "nothing happened" from "exactly one
        # full lap" (ptr == old_ptr either way) — after a lap EVERY slot
        # was overwritten and all in-flight priorities must be dropped.
        self.ptr_advances = 0
        self.size = 0
        self.env_steps = 0
        self.num_episodes = 0
        self.episode_reward_sum = 0.0
        # run-lifetime totals (never reset by pop_episode_stats)
        self.total_episodes = 0
        self.total_reward_sum = 0.0
        self.learning_sum = np.zeros(cfg.num_blocks, np.int64)
        self.occupied = np.zeros(cfg.num_blocks, bool)
        self.num_seq_store = np.zeros(cfg.num_blocks, np.int32)
        # Disk-tier mode only (TieredReplayBuffer allocates it, sized
        # host+disk blocks): per-slot last-mutation stamp in ptr_advances
        # clock units. The pointer-window staleness mask below assumes
        # slots are overwritten in ring order; priority-aware demotion
        # moves block contents between ARBITRARY slots, so in disk mode
        # every mutation (write, demote, retire) stamps its slot and
        # update_priorities compares stamps instead of windows. None on
        # every non-disk plane — the window mask and its exact byte
        # behavior are untouched.
        self.slot_stamp = None
        # priority_plane="device": an HBM float32 mirror of the tree
        # (replay/device_sum_tree.DeviceSumTree) attached by the owning
        # data plane. Every host-side tree write goes through _tree_write,
        # which keeps the mirror in sync. All mirror writes happen under
        # self.lock — the same lock the data plane holds while dispatching
        # a learner superstep and installing its output tree — so device
        # tree mutations enqueue in lock-acquisition order and the device
        # stream serializes them exactly like the host tree: ingestion
        # dispatched after a superstep lands ON TOP of its write-backs,
        # which is precisely the verdict the host pointer-window mask
        # reaches for slots overwritten during a round trip.
        self.dtree = None
        self.lock = threading.Lock()

    def attach_device_tree(self, dtree) -> None:
        self.dtree = dtree

    def _tree_write(self, idxes: np.ndarray, td_errors: np.ndarray) -> None:
        """The single funnel for host-initiated tree writes (ingestion,
        retirement, drained priorities). Caller holds the lock."""
        self.tree.update(idxes, td_errors)
        if self.dtree is not None:
            self.dtree.update(idxes, td_errors)

    def __len__(self) -> int:
        return self.size

    def can_sample(self) -> bool:
        return self.size >= self.cfg.learning_starts

    # --- accounting (call with self.lock held) ----------------------------

    # r2d2: guarded-by(lock)
    def _account_block_at(
        self, slot: int, num_sequences: int, learning_total: int,
        priorities: np.ndarray, episode_reward: Optional[float],
    ) -> None:
        """Tree + counter bookkeeping for a block at an explicit slot; does
        NOT move the ring pointer (the caller owns pointer protocol — either
        _account_add's advance-after or _reserve_advance's advance-before).
        Caller holds the lock."""
        S = self.cfg.seqs_per_block
        idxes = np.arange(slot * S, (slot + 1) * S, dtype=np.int64)
        self._tree_write(idxes, priorities)
        if self.occupied[slot]:
            self.size -= int(self.learning_sum[slot])
        self.learning_sum[slot] = learning_total
        self.occupied[slot] = True
        self.num_seq_store[slot] = num_sequences
        self.size += learning_total
        self.env_steps += learning_total
        if episode_reward is not None:
            self.episode_reward_sum += episode_reward
            self.num_episodes += 1
            self.total_episodes += 1
            self.total_reward_sum += episode_reward

    def _account_add(
        self, num_sequences: int, learning_total: int, priorities: np.ndarray,
        episode_reward: Optional[float],
    ) -> int:
        """Update tree + counters for a block landing at block_ptr; returns
        the slot index written. Caller holds the lock and writes the data
        plane for the same slot."""
        ptr = self.block_ptr
        self._account_block_at(
            ptr, num_sequences, learning_total, priorities, episode_reward
        )
        self.block_ptr = (ptr + 1) % self.cfg.num_blocks
        self.ptr_advances += 1
        if self.slot_stamp is not None:
            self.slot_stamp[ptr] = self.ptr_advances
        return ptr

    def _account_blocks(
        self,
        num_seq: np.ndarray,
        learning_totals: np.ndarray,
        priorities: np.ndarray,
        episode_rewards: np.ndarray,
        dones: np.ndarray,
    ) -> None:
        """Account a batch of blocks written at consecutive ring slots
        (shared by every batched-write path: the one place that knows a
        truncated chunk is not a finished episode). Caller holds the lock
        and has already written the data plane."""
        for i in range(len(num_seq)):
            self._account_add(
                int(num_seq[i]),
                int(learning_totals[i]),
                priorities[i],
                float(episode_rewards[i]) if dones[i] else None,
            )

    def _retire_slots(self, slots: np.ndarray) -> None:
        """Evict the blocks at `slots` from the tree and the size
        accounting (priorities zeroed: they can never be sampled again).
        Caller holds the lock."""
        occ = slots[self.occupied[slots]]
        if occ.size:
            S = self.cfg.seqs_per_block
            idxes = (occ[:, None] * S + np.arange(S)[None, :]).ravel()
            self._tree_write(idxes, np.zeros(idxes.size, np.float32))
            self.size -= int(self.learning_sum[occ].sum())
            self.learning_sum[occ] = 0
            self.occupied[occ] = False
            self.num_seq_store[occ] = 0
        if self.slot_stamp is not None and slots.size:
            # disk mode: retirement is a mutation like any other — bump
            # the clock once and stamp so in-flight priority write-backs
            # for these slots are rejected by the stamp comparison
            self.ptr_advances += 1
            self.slot_stamp[slots] = self.ptr_advances

    def _reserve_contiguous(self, n: int) -> int:
        """Wrap the ring pointer to 0 if fewer than n slots remain before
        the end, and return the pointer: the caller writes slots
        [ptr, ptr+n) as ONE contiguous slab (a dynamic_update_slice — a
        ring-crossing scatter is ~20x slower on TPU). The skipped tail
        slots are RETIRED: with a steady E-batch writer the pointer cycle
        repeats every lap, so the tail would otherwise hold frozen,
        never-evicted blocks — instead their priorities are zeroed and
        their transitions leave the size accounting, shrinking effective
        capacity to floor(num_blocks/n)*n for batch writers. The
        pointer-window staleness mask treats the whole tail as overwritten
        — over-rejection, never wrong. Caller holds the lock."""
        nb = self.cfg.num_blocks
        if self.block_ptr + n > nb:
            self._retire_slots(np.arange(self.block_ptr, nb))
            # the jump traverses the tail: it counts toward lap detection
            self.ptr_advances += nb - self.block_ptr
            self.block_ptr = 0
        return self.block_ptr

    def _reserve_advance(self, n: int) -> int:
        """Reserve n contiguous slots AND advance the ring pointer past
        them, retiring the slots' previous blocks immediately. For writers
        that defer the new blocks' accounting (FusedSystemRunner's
        one-dispatch-lag chunk readback): after this returns, (a) draws
        cannot target the reserved slots (leaves are zero), and (b) the
        pointer-window staleness mask already treats them as overwritten —
        so priority rows and the chunk's own accounting can land in any
        order later, via _account_blocks_at. Caller holds the lock."""
        ptr0 = self._reserve_contiguous(n)
        self._retire_slots(np.arange(ptr0, ptr0 + n))
        self.block_ptr = (ptr0 + n) % self.cfg.num_blocks
        self.ptr_advances += n
        return ptr0

    def _account_blocks_at(
        self,
        ptr0: int,
        num_seq: np.ndarray,
        learning_totals: np.ndarray,
        priorities: np.ndarray,
        episode_rewards: np.ndarray,
        dones: np.ndarray,
    ) -> None:
        """Deferred accounting for blocks written at slots [ptr0, ptr0+E)
        previously reserved via _reserve_advance (pointer already past
        them). Caller holds the lock; the data plane was written by the
        dispatch that the reservation preceded."""
        for i in range(len(num_seq)):
            self._account_block_at(
                ptr0 + i,
                int(num_seq[i]),
                int(learning_totals[i]),
                priorities[i],
                float(episode_rewards[i]) if dones[i] else None,
            )

    def _draw(self, rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Stratified draw of batch_size sequence coordinates (with the
        zero-leaf clamp reflected into the returned global idxes). Caller
        holds the lock. Returns (b, s, idxes, is_weights)."""
        S = self.cfg.seqs_per_block
        idxes, is_weights = self.tree.sample(self.cfg.batch_size, rng)
        b = idxes // S
        s = np.minimum(idxes % S, np.maximum(self.num_seq_store[b] - 1, 0))
        return b, s, b * S + s, is_weights

    # --- priorities -------------------------------------------------------

    def update_priorities(
        self,
        idxes: np.ndarray,
        td_errors: np.ndarray,
        old_ptr: int,
        old_advances: Optional[int] = None,
    ) -> None:
        """Apply learner priorities, discarding any index overwritten during
        the sample->train round trip (worker.py:290-307 invariant).

        old_advances: the draw-time ptr_advances stamp. When provided, a
        FULL ring lap between draw and apply (every slot overwritten, the
        wrapped pointer back at old_ptr — invisible to the window mask)
        rejects the whole batch. Callers without the stamp keep the
        window-mask-only behavior (the reference's own guarantee)."""
        S = self.cfg.seqs_per_block
        # offered / applied: the replay layer's useful-outcomes-over-attempts
        # ratio (the mask and the full-lap check discard silently)
        count("replay.priority_rows_offered", len(idxes))
        with self.lock:
            if self.slot_stamp is not None and old_advances is not None:
                # Disk mode: demotion moves blocks between arbitrary slots,
                # so ring-window reasoning is void. A per-slot stamp gives
                # the EXACT verdict: keep an index iff its slot has not
                # mutated since the draw. (The full-lap check below would
                # also misfire here — demotions bump ptr_advances without
                # overwriting every slot.)
                mask = self.slot_stamp[idxes // S] <= old_advances
                self._tree_write(idxes[mask], td_errors[mask])
                count("replay.priority_rows_applied", int(mask.sum()))
                return
            if (
                old_advances is not None
                and self.ptr_advances - old_advances >= self.cfg.num_blocks
            ):
                return
            ptr = self.block_ptr
            if ptr > old_ptr:
                mask = (idxes < old_ptr * S) | (idxes >= ptr * S)
            elif ptr < old_ptr:
                mask = (idxes < old_ptr * S) & (idxes >= ptr * S)
            else:
                mask = np.ones_like(idxes, dtype=bool)
            self._tree_write(idxes[mask], td_errors[mask])
            count("replay.priority_rows_applied", int(mask.sum()))

    def pop_episode_stats(self):
        with self.lock:
            n, r = self.num_episodes, self.episode_reward_sum
            self.num_episodes = 0
            self.episode_reward_sum = 0.0
        return n, r

    def episode_totals(self):
        """Run-lifetime (episodes, reward_sum) — unaffected by the
        pop-and-reset logging stream."""
        with self.lock:
            return self.total_episodes, self.total_reward_sum
