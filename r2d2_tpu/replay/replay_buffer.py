"""Central sequence-prioritized replay with vectorized batch assembly
(host data plane).

Capability parity with the reference ReplayBuffer (reference
worker.py:69-310): circular store of fixed-size blocks, a sum tree over all
sequence slots, stratified prioritized sampling with IS weights, and
stale-priority rejection via pointer-window masking (the control logic
lives in replay/control_plane.py, shared with the HBM-resident variant).

TPU-first redesign: the reference assembles each batch with a 64-iteration
Python loop of per-sequence tensor slices plus `pad_sequence`
(worker.py:210-288). Here every block field lives in ONE preallocated numpy
array, and a batch is assembled with a single fancy-index gather per field —
(batch, seq_len) windows come out fixed-shape (jit-stable) in a handful of
vectorized ops.

When host->device bandwidth is the binding constraint, prefer
replay/device_store.DeviceReplayBuffer, which keeps the data plane in HBM.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from r2d2_tpu.config import R2D2Config
from r2d2_tpu.replay.block import Block, store_field_specs
from r2d2_tpu.replay.control_plane import ReplayControlPlane


@dataclasses.dataclass
class SampledBatch:
    """Fixed-shape training batch (host numpy, ready for device_put)."""

    obs: np.ndarray            # (B, seq_len, *obs_shape) uint8
    last_action: np.ndarray    # (B, seq_len) uint8 scalar actions
    last_reward: np.ndarray    # (B, seq_len) float32
    hidden: np.ndarray         # (B, *state_shape) cfg.state_dtype (f32 | bf16)
    action: np.ndarray         # (B, L) int32
    n_step_reward: np.ndarray  # (B, L) float32
    gamma: np.ndarray          # (B, L) float32
    burn_in_steps: np.ndarray  # (B,) int32
    learning_steps: np.ndarray # (B,) int32
    forward_steps: np.ndarray  # (B,) int32
    is_weights: np.ndarray     # (B,) float32
    idxes: np.ndarray          # (B,) int64 — sequence slots, for priority updates
    old_ptr: int               # block pointer at sample time (staleness check)
    env_steps: int             # total env steps stored so far
    # ptr_advances stamp (full-lap detection); None = no lap check
    old_advances: Optional[int] = None
    # (B,) int32 per-sequence task ids on multi-task configs; None on the
    # single-task golden path (keeps DeviceBatch.from_sampled's pytree —
    # and thus every donation/jaxpr contract over it — unchanged)
    task: Optional[np.ndarray] = None


class ReplayBuffer(ReplayControlPlane):
    def __init__(self, cfg: R2D2Config, native: Optional[object] = None):
        super().__init__(cfg, native=native)
        S = cfg.seqs_per_block
        nb, slot = cfg.num_blocks, cfg.block_slot_len

        self.obs_store = np.zeros((nb, slot, *cfg.obs_shape), dtype=np.uint8)
        self.last_action_store = np.zeros((nb, slot), dtype=np.uint8)
        self.last_reward_store = np.zeros((nb, slot), dtype=np.float32)
        self.action_store = np.zeros((nb, cfg.block_length), dtype=np.uint8)
        self.n_step_reward_store = np.zeros((nb, cfg.block_length), dtype=np.float32)
        self.gamma_store = np.zeros((nb, cfg.block_length), dtype=np.float32)
        # cfg.state_dtype: float32, or bfloat16 under precision="bf16" —
        # halves the carry slab and every sampled batch's hidden bytes
        # (block.hidden arrives float32; the slab assignment downcasts)
        hidden_shape, hidden_dtype = store_field_specs(cfg)["hidden"]
        self.hidden_store = np.zeros((nb, *hidden_shape), dtype=hidden_dtype)
        self.burn_in_store = np.zeros((nb, S), dtype=np.int32)
        self.learning_store = np.zeros((nb, S), dtype=np.int32)
        self.forward_store = np.zeros((nb, S), dtype=np.int32)
        # scalar per block (one actor collects one task); (nb,) is cheap
        # enough to keep unconditionally — sampling only SURFACES it on
        # multi-task configs (SampledBatch.task stays None otherwise)
        self.task_store = np.zeros((nb,), dtype=np.int32)

    # ------------------------------------------------------------------ add

    def _write_block_locked(self, block: Block, ptr: int) -> None:
        """Write one block's data-plane fields into slab slot `ptr`.
        Caller holds self.lock and owns the accounting that follows.
        (Factored so the tiered store's disk-demotion overrides can reuse
        the exact slab-write byte behavior without re-entering the lock —
        threading.Lock is not reentrant.)"""
        S = self.cfg.seqs_per_block
        steps = block.stored_steps
        self.obs_store[ptr, :steps] = block.obs
        self.last_action_store[ptr, :steps] = block.last_action
        self.last_reward_store[ptr, :steps] = block.last_reward
        T = len(block.action)
        self.action_store[ptr, :T] = block.action
        self.n_step_reward_store[ptr, :T] = block.n_step_reward
        self.gamma_store[ptr, :T] = block.gamma
        ns = block.num_sequences
        self.hidden_store[ptr, :ns] = block.hidden
        self.burn_in_store[ptr, :S] = 0
        self.learning_store[ptr, :S] = 0
        self.forward_store[ptr, :S] = 0
        self.burn_in_store[ptr, :ns] = block.burn_in_steps
        self.learning_store[ptr, :ns] = block.learning_steps
        self.forward_store[ptr, :ns] = block.forward_steps
        self.task_store[ptr] = block.task

    def add_block(
        self, block: Block, priorities: np.ndarray, episode_reward: Optional[float]
    ) -> None:
        """Write one block into the circular store and refresh its leaves
        (reference worker.py:178-208). `priorities` must already be padded
        to seqs_per_block (zeros for absent sequences)."""
        with self.lock:
            # data writes FIRST, accounting last: a malformed block (flaky
            # env shapes) raises here before the tree/pointer mutate, so a
            # supervised-restart run can never train on a slot whose
            # priorities describe data that was never written
            self._write_block_locked(block, self.block_ptr)
            self._account_add(
                block.num_sequences, int(block.learning_steps.sum()), priorities, episode_reward
            )

    def add_blocks_batch(self, items) -> None:
        """Write a list of (block, priorities, episode_reward) triples in
        one pass. The live-loop ingestion bridge's entry point: draining a
        burst under a single lock acquisition instead of one per block
        keeps the learner's sample path from interleaving tree refreshes
        with every store write. Semantically identical to calling
        add_block per item, in order."""
        with self.lock:
            for block, priorities, episode_reward in items:
                self._write_block_locked(block, self.block_ptr)
                self._account_add(
                    block.num_sequences, int(block.learning_steps.sum()),
                    priorities, episode_reward,
                )

    # --------------------------------------------------------------- sample

    def sample_batch(self, rng: np.random.Generator) -> SampledBatch:
        """Draw a fixed-shape batch via stratified prioritized sampling.

        All per-field gathers are single vectorized fancy-index reads over
        the preallocated stores — the TPU-feeding rewrite of reference
        worker.py:210-288.
        """
        cfg = self.cfg
        L = cfg.learning_steps
        with self.lock:
            b, s, idxes, is_weights = self._draw(rng)

            burn = self.burn_in_store[b, s]
            learn = self.learning_store[b, s]
            fwd = self.forward_store[b, s]
            first_burn = self.burn_in_store[b, 0]
            start = first_burn + s * L          # buffer coords of learning start
            win_start = start - burn

            if self.native is not None:
                # C++ memcpy gather (clamped-window batch assembly,
                # _native/replay_core.cpp) — one call per field.
                g = self.native.gather_windows
                T = cfg.seq_len
                obs = g(self.obs_store, b, win_start, T)
                last_action = g(self.last_action_store, b, win_start, T)
                last_reward = g(self.last_reward_store, b, win_start, T)
                lstart = s * L
                action = g(self.action_store, b, lstart, L).astype(np.int32)
                n_step_reward = g(self.n_step_reward_store, b, lstart, L)
                gamma = g(self.gamma_store, b, lstart, L)
            else:
                t = np.arange(cfg.seq_len)
                rows = win_start[:, None] + t[None, :]
                np.clip(rows, 0, cfg.block_slot_len - 1, out=rows)
                bcol = b[:, None]
                obs = self.obs_store[bcol, rows]
                last_action = self.last_action_store[bcol, rows]
                last_reward = self.last_reward_store[bcol, rows]

                tl = np.arange(L)
                lrows = s[:, None] * L + tl[None, :]
                np.clip(lrows, 0, cfg.block_length - 1, out=lrows)
                action = self.action_store[bcol, lrows].astype(np.int32)
                n_step_reward = self.n_step_reward_store[bcol, lrows]
                gamma = self.gamma_store[bcol, lrows]

            hidden = self.hidden_store[b, s]

            batch = SampledBatch(
                obs=obs,
                last_action=last_action,
                last_reward=last_reward,
                hidden=hidden,
                action=action,
                n_step_reward=n_step_reward,
                gamma=gamma,
                burn_in_steps=burn.astype(np.int32),
                learning_steps=learn.astype(np.int32),
                forward_steps=fwd.astype(np.int32),
                is_weights=is_weights,
                idxes=idxes,
                old_ptr=self.block_ptr,
                env_steps=self.env_steps,
                old_advances=self.ptr_advances,
                task=self.task_store[b] if cfg.num_tasks > 1 else None,
            )
        return batch
