"""Tiered replay plane, L3 half: full-capacity host store + HBM staging.

The capacity/throughput dilemma this closes: the HBM plane
(replay/device_store.py) feeds the step from on-chip data but only at
capacities that fit there, while the host plane holds the paper's full
2x10^6 transitions but pays a blocking host->device copy of every batch
plus per-field transfer latency, serialized ahead of its update.

Tiering splits the difference:

- The RESIDENT tier is the host-RAM slab store, unchanged from
  ReplayBuffer (same preallocated per-field arrays, same add_block, same
  shared control plane) — np.zeros allocation is lazy on Linux, so a 2M
  config costs physical pages only for the filled prefix.
- The STAGING tier is a pair of HBM slabs holding K sample-batches'
  gathered windows each. `sample_window_stack` draws K batches under ONE
  control-plane lock hold and gathers ALL their sequence windows in one
  vectorized pass: the (K, B) coordinates are flattened and each field
  GROUP crosses into the native core once (gather_windows_multi,
  _native/replay_core.cpp) — host assembly is memcpy-bound, not
  Python-loop-bound. `stage_chunk` then starts one async `device_put` of
  the whole stacked pytree; TieredPrefetchPipeline runs that on a staging
  thread so the transfer of chunk k+1 executes while the learner's fused
  K-update scan (learner.make_stacked_batch_train_step) consumes chunk k.

Staleness is applied AT STAGE TIME: the gather copies bytes out of the
resident tier under the lock, so a staged chunk can never be invalidated
by a concurrent block write — there is nothing pointer-like left in it.
The old_ptr/old_advances stamps captured in the same lock hold ride along
so the deferred priority write-back still passes through the standard
pointer-window mask (control_plane.update_priorities): rows whose slots
were overwritten between stage and write-back are dropped, never
mis-applied.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import queue
import threading
import time
from typing import Optional

import numpy as np

from r2d2_tpu.replay.block import store_field_specs
from r2d2_tpu.replay.disk_tier import DiskTier
from r2d2_tpu.replay.replay_buffer import ReplayBuffer, SampledBatch
from r2d2_tpu.replay.sum_tree import SumTree
from r2d2_tpu.utils.faults import fault_point, with_retries

# decoded disk records kept hot on the staging thread: repeated draws of a
# high-priority demoted block skip the page-in + inflate after the first
_DISK_CACHE_RECORDS = 64


@dataclasses.dataclass
class StagedWindows:
    """K sample-batches' windows, stacked (K, B, ...) on host — the field
    set of SampledBatch with a leading K axis, plus the stage-time stamps
    shared by the whole chunk (all K draws happen under one lock hold)."""

    obs: np.ndarray            # (K, B, seq_len, *obs_shape) uint8
    last_action: np.ndarray    # (K, B, seq_len) uint8
    last_reward: np.ndarray    # (K, B, seq_len) float32
    hidden: np.ndarray         # (K, B, *state_shape) cfg.state_dtype
    action: np.ndarray         # (K, B, L) int32
    n_step_reward: np.ndarray  # (K, B, L) float32
    gamma: np.ndarray          # (K, B, L) float32
    burn_in_steps: np.ndarray  # (K, B) int32
    learning_steps: np.ndarray # (K, B) int32
    forward_steps: np.ndarray  # (K, B) int32
    is_weights: np.ndarray     # (K, B) float32
    idxes: np.ndarray          # (K, B) int64 — for the priority write-back
    old_ptr: int
    env_steps: int
    old_advances: int

    def nbytes(self) -> int:
        return sum(
            getattr(self, f.name).nbytes
            for f in dataclasses.fields(self)
            if f.name not in ("old_ptr", "env_steps", "old_advances")
        )


@dataclasses.dataclass
class StagedChunk:
    """A StagedWindows after lift-off: `batch` is a stacked
    learner.DeviceBatch (leaves (K, B, ...)) whose device_put has been
    started; the stamps stay host-side for the priority write-back."""

    batch: object
    idxes: np.ndarray
    old_ptr: int
    old_advances: int
    env_steps: int
    # the sampling RNG's bit-generator state captured BEFORE this chunk's
    # draws — the rewind point if the chunk is discarded at preemption
    # (TieredPrefetchPipeline.stop(rewind=True))
    rng_state: Optional[dict] = None


class TieredReplayBuffer(ReplayBuffer):
    """ReplayBuffer (full-capacity host data plane, shared control plane)
    plus the vectorized K-batch window gather the staging tier feeds on.

    The single-batch `sample_batch` path is inherited untouched — it is the
    executable spec `sample_window_stack` must match bit-for-bit (pinned by
    tests/test_tiered_store.py): same RNG stream consumption (K stratified
    tree draws in order), same clamp semantics, same dtypes, same stamps.

    Disk tier (cfg.replay_disk_capacity > 0, default OFF = everything above
    byte-identical): a third storage level below the host slab
    (replay/disk_tier.py). Logical block ids split in two: [0, num_blocks)
    live in the host slab, [num_blocks, num_blocks + disk_blocks) in mmap
    segment records. The control plane covers BOTH ranges — one sum tree,
    extended occupancy/accounting arrays, and RAM-resident per-sequence
    metadata (hidden carries, spans, task) for every logical block — so a
    demoted block's leaves stay live and it samples like any other; only
    its six per-step fields live on disk, decoded through an LRU cache on
    the staging thread where the H2D double buffer hides the page-in.

    Demotion is priority-aware, not oldest-first: when the ring pointer
    lands on an occupied slab slot, the LOWEST-priority occupied host block
    spills to the disk ring (its slab slot inherits the pointer occupant so
    the incoming block can land at the pointer, preserving ring-write
    semantics for every producer); true eviction happens only when the disk
    ring itself wraps onto a live record. Slot moves void the pointer-window
    staleness reasoning, so disk mode switches update_priorities to the
    per-slot stamp clock (control_plane.slot_stamp)."""

    def __init__(self, cfg, native=None):
        super().__init__(cfg, native=native)
        self.disk: Optional[DiskTier] = None
        self._disk_ptr = 0
        self._demotions = 0
        self._evictions = 0
        self._disk_cache: "collections.OrderedDict[int, dict]" = (
            collections.OrderedDict()
        )
        if cfg.replay_disk_capacity <= 0:
            return
        self.disk = DiskTier(cfg)
        nb, S = cfg.num_blocks, cfg.seqs_per_block
        total = nb + self.disk.disk_blocks
        # control plane grows to cover disk-resident sequences: leaves for
        # demoted blocks stay LIVE in the tree (that is what keeps them
        # sampleable), and the per-sequence metadata stores extend so
        # sampling coordinates resolve without touching a segment. The
        # extra leaves start at zero, so draws/IS-weights are bit-identical
        # to the undecorated tree until something actually demotes.
        self.tree = SumTree(
            total * S, cfg.prio_exponent, cfg.is_exponent, native=self.native
        )
        self.learning_sum = np.zeros(total, np.int64)
        self.occupied = np.zeros(total, bool)
        self.num_seq_store = np.zeros(total, np.int32)
        self.slot_stamp = np.zeros(total, np.int64)
        hidden_shape, hidden_dtype = store_field_specs(cfg)["hidden"]
        self.hidden_store = np.zeros((total, *hidden_shape), dtype=hidden_dtype)
        self.burn_in_store = np.zeros((total, S), dtype=np.int32)
        self.learning_store = np.zeros((total, S), dtype=np.int32)
        self.forward_store = np.zeros((total, S), dtype=np.int32)
        self.task_store = np.zeros((total,), dtype=np.int32)

    # ------------------------------------------------------- disk-tier spill

    def add_block(self, block, priorities, episode_reward) -> None:
        if self.disk is None:
            super().add_block(block, priorities, episode_reward)
            return
        with self.lock:
            if self.occupied[self.block_ptr]:
                self._spill_lowest(self.block_ptr)
            self._write_block_locked(block, self.block_ptr)
            self._account_add(
                block.num_sequences, int(block.learning_steps.sum()),
                priorities, episode_reward,
            )

    def add_blocks_batch(self, items) -> None:
        if self.disk is None:
            super().add_blocks_batch(items)
            return
        with self.lock:
            for block, priorities, episode_reward in items:
                if self.occupied[self.block_ptr]:
                    self._spill_lowest(self.block_ptr)
                self._write_block_locked(block, self.block_ptr)
                self._account_add(
                    block.num_sequences, int(block.learning_steps.sum()),
                    priorities, episode_reward,
                )

    def _spill_lowest(self, ptr: int) -> None:
        """Demote the lowest-priority occupied host block to the disk ring,
        leaving slab slot `ptr` free for the incoming block. Caller holds
        the lock. Crash ordering (chaos-tested at disk.write): retire the
        disk slot's old occupant FIRST, write the segment record, only then
        move accounting — a kill at any point leaves every referenced
        record intact."""
        cfg = self.cfg
        nb, S = cfg.num_blocks, cfg.seqs_per_block
        leaf = self.tree.priorities_of(
            np.arange(nb * S, dtype=np.int64)
        ).reshape(nb, S)
        score = np.where(self.occupied[:nb], leaf.max(axis=1), np.inf)
        victim = int(np.argmin(score))
        dslot = self._disk_ptr
        dl = nb + dslot
        if self.occupied[dl]:
            # true eviction: the disk ring wrapped onto a live record
            self._retire_slots(np.array([dl]))
            self._evictions += 1
        self._disk_cache.pop(dslot, None)
        # segment write (fault_point("disk.write") fires inside, BEFORE the
        # bytes land): the victim is still fully accounted at its host slot
        # if the process dies here
        self.disk.write_block(dslot, {
            "obs": self.obs_store[victim],
            "last_action": self.last_action_store[victim],
            "last_reward": self.last_reward_store[victim],
            "action": self.action_store[victim],
            "n_step_reward": self.n_step_reward_store[victim],
            "gamma": self.gamma_store[victim],
        })
        # move the victim's control-plane state to the disk slot. Leaves
        # move RAW (already ^alpha): tree.update would re-apply the
        # exponent. No device mirror to sync — priority_plane="device" is
        # rejected with the disk tier at validate().
        vidx = np.arange(victim * S, (victim + 1) * S, dtype=np.int64)
        self.tree.set_raw(
            np.arange(dl * S, (dl + 1) * S, dtype=np.int64),
            self.tree.priorities_of(vidx),
        )
        self.learning_sum[dl] = self.learning_sum[victim]
        self.occupied[dl] = True
        self.num_seq_store[dl] = self.num_seq_store[victim]
        self.hidden_store[dl] = self.hidden_store[victim]
        self.burn_in_store[dl] = self.burn_in_store[victim]
        self.learning_store[dl] = self.learning_store[victim]
        self.forward_store[dl] = self.forward_store[victim]
        self.task_store[dl] = self.task_store[victim]
        if victim != ptr:
            # ring preservation: the pointer occupant moves into the
            # victim's freed slab slot so the incoming block lands at the
            # pointer like every writer assumes
            for name in ("obs", "last_action", "last_reward", "action",
                         "n_step_reward", "gamma", "hidden", "burn_in",
                         "learning", "forward", "task"):
                store = getattr(self, name + "_store")
                store[victim] = store[ptr]
            pidx = np.arange(ptr * S, (ptr + 1) * S, dtype=np.int64)
            self.tree.set_raw(vidx, self.tree.priorities_of(pidx))
            self.tree.set_raw(pidx, np.zeros(S))
            self.learning_sum[victim] = self.learning_sum[ptr]
            self.num_seq_store[victim] = self.num_seq_store[ptr]
        else:
            self.tree.set_raw(vidx, np.zeros(S))
        self.learning_sum[ptr] = 0
        self.num_seq_store[ptr] = 0
        self.occupied[ptr] = False
        # size is unchanged on purpose: the demoted block stays sampleable.
        # Every touched slot stamps the mutation clock so in-flight
        # priority write-backs aimed at the old occupants are dropped.
        self.ptr_advances += 1
        self.slot_stamp[[victim, dl, ptr]] = self.ptr_advances
        self._disk_ptr = (dslot + 1) % self.disk.disk_blocks
        self._demotions += 1

    def _disk_record(self, dslot: int) -> dict:
        """Decoded record for disk ring slot `dslot`, through the LRU
        cache. Caller holds the lock (staging thread)."""
        rec = self._disk_cache.get(dslot)
        if rec is None:
            rec = self.disk.read_block(dslot)
            self._disk_cache[dslot] = rec
            while len(self._disk_cache) > _DISK_CACHE_RECORDS:
                self._disk_cache.popitem(last=False)
        else:
            self._disk_cache.move_to_end(dslot)
        return rec

    def _fill_disk_rows(self, b, win_start, lstart, obs, last_action,
                        last_reward, action, n_step_reward, gamma) -> None:
        """Overwrite the rows of a gathered window stack whose draws landed
        on disk-resident blocks: page in + decode through the mmap on the
        staging thread (the H2D double buffer hides it from the learner).
        Clamp semantics mirror the slab gather exactly, so a window sampled
        from a demoted block is bit-identical to the same window before
        demotion."""
        cfg = self.cfg
        nb = cfg.num_blocks
        t = np.arange(cfg.seq_len)
        tl = np.arange(cfg.learning_steps)
        for i in np.nonzero(b >= nb)[0]:
            rec = self._disk_record(int(b[i]) - nb)
            rows = np.clip(win_start[i] + t, 0, cfg.block_slot_len - 1)
            obs[i] = rec["obs"][rows]
            last_action[i] = rec["last_action"][rows]
            last_reward[i] = rec["last_reward"][rows]
            lrows = np.clip(lstart[i] + tl, 0, cfg.block_length - 1)
            action[i] = rec["action"][lrows].astype(np.int32)
            n_step_reward[i] = rec["n_step_reward"][lrows]
            gamma[i] = rec["gamma"][lrows]

    def sample_batch(self, rng: np.random.Generator) -> SampledBatch:
        if self.disk is None:
            return super().sample_batch(rng)
        # one-chunk window stack: same RNG consumption, same clamps, same
        # stamps as the inherited path, plus the disk-row fixup
        sw = self.sample_window_stack(rng, 1)
        task = None
        if self.cfg.num_tasks > 1:
            task = self.task_store[sw.idxes[0] // self.cfg.seqs_per_block]
        return SampledBatch(
            obs=sw.obs[0], last_action=sw.last_action[0],
            last_reward=sw.last_reward[0], hidden=sw.hidden[0],
            action=sw.action[0], n_step_reward=sw.n_step_reward[0],
            gamma=sw.gamma[0], burn_in_steps=sw.burn_in_steps[0],
            learning_steps=sw.learning_steps[0],
            forward_steps=sw.forward_steps[0],
            is_weights=sw.is_weights[0], idxes=sw.idxes[0],
            old_ptr=sw.old_ptr, env_steps=sw.env_steps,
            old_advances=sw.old_advances, task=task,
        )

    def disk_stats(self) -> dict:
        """Disk-tier counters for the logging/bench plane ({} when off)."""
        if self.disk is None:
            return {}
        with self.lock:
            st = self.disk.stats()
            st["disk_occupied"] = int(
                self.occupied[self.cfg.num_blocks:].sum()
            )
            st["disk_demotions"] = self._demotions
            st["disk_evictions"] = self._evictions
        return st

    def sample_window_stack(self, rng: np.random.Generator, k: int) -> StagedWindows:
        cfg = self.cfg
        L, T, B = cfg.learning_steps, cfg.seq_len, cfg.batch_size
        with self.lock:
            draws = [self._draw(rng) for _ in range(k)]
            # flattened (K*B,) coordinates: one gather per field group
            b = np.concatenate([d[0] for d in draws])
            s = np.concatenate([d[1] for d in draws])
            idxes = np.stack([d[2] for d in draws])
            is_weights = np.stack([d[3] for d in draws])

            burn = self.burn_in_store[b, s]
            learn = self.learning_store[b, s]
            fwd = self.forward_store[b, s]
            first_burn = self.burn_in_store[b, 0]
            win_start = first_burn + s * L - burn
            lstart = s * L

            # disk mode: per-step fields of disk-resident draws cannot come
            # from the slab — remap those coordinates to row 0 for the bulk
            # gather (cheap garbage) and overwrite them from the decoded
            # records below. Per-sequence metadata above indexed the real
            # (extended) stores already.
            bg = b if self.disk is None else np.minimum(b, cfg.num_blocks - 1)
            if self.native is not None:
                obs, last_action, last_reward = self.native.gather_windows_multi(
                    [self.obs_store, self.last_action_store, self.last_reward_store],
                    bg, win_start, T,
                )
                action, n_step_reward, gamma = self.native.gather_windows_multi(
                    [self.action_store, self.n_step_reward_store, self.gamma_store],
                    bg, lstart, L,
                )
                action = action.astype(np.int32)
            else:
                t = np.arange(T)
                rows = win_start[:, None] + t[None, :]
                np.clip(rows, 0, cfg.block_slot_len - 1, out=rows)
                bcol = bg[:, None]
                obs = self.obs_store[bcol, rows]
                last_action = self.last_action_store[bcol, rows]
                last_reward = self.last_reward_store[bcol, rows]
                tl = np.arange(L)
                lrows = lstart[:, None] + tl[None, :]
                np.clip(lrows, 0, cfg.block_length - 1, out=lrows)
                action = self.action_store[bcol, lrows].astype(np.int32)
                n_step_reward = self.n_step_reward_store[bcol, lrows]
                gamma = self.gamma_store[bcol, lrows]

            if self.disk is not None:
                self._fill_disk_rows(
                    b, win_start, lstart, obs, last_action, last_reward,
                    action, n_step_reward, gamma,
                )

            hidden = self.hidden_store[b, s]
            old_ptr = self.block_ptr
            env_steps = self.env_steps
            old_advances = self.ptr_advances

        def kb(x):
            return x.reshape(k, B, *x.shape[1:])

        return StagedWindows(
            obs=kb(obs),
            last_action=kb(last_action),
            last_reward=kb(last_reward),
            hidden=kb(hidden),
            action=kb(action),
            n_step_reward=kb(n_step_reward),
            gamma=kb(gamma),
            burn_in_steps=kb(burn.astype(np.int32)),
            learning_steps=kb(learn.astype(np.int32)),
            forward_steps=kb(fwd.astype(np.int32)),
            is_weights=is_weights,
            idxes=idxes,
            old_ptr=old_ptr,
            env_steps=env_steps,
            old_advances=old_advances,
        )


def stage_chunk(replay: TieredReplayBuffer, rng: np.random.Generator, k: int,
                timer=None) -> StagedChunk:
    """Draw + host-gather + lift one K-batch chunk into HBM.

    The device_put covers the whole stacked pytree in one call (one
    transfer program, not 11 per update like the inline host plane), and
    the trailing block_until_ready makes the h2d span measure true
    transfer completion — callers run this off the critical path (staging
    thread), so blocking here costs the consumer nothing. `timer` is a
    utils.profiling.TransferTimer or None."""
    import jax

    from r2d2_tpu.learner import DeviceBatch

    pre_state = rng.bit_generator.state
    sw = replay.sample_window_stack(rng, k)

    def lift():
        fault_point("tiered.stage_h2d")
        batch = jax.device_put(DeviceBatch(
            obs=sw.obs,
            last_action=sw.last_action.astype(np.int32),
            last_reward=sw.last_reward,
            hidden=sw.hidden,
            action=sw.action,
            n_step_reward=sw.n_step_reward,
            gamma=sw.gamma,
            burn_in_steps=sw.burn_in_steps,
            learning_steps=sw.learning_steps,
            forward_steps=sw.forward_steps,
            is_weights=sw.is_weights,
        ))
        jax.block_until_ready(batch)
        return batch

    cm = timer.h2d(sw.nbytes()) if timer is not None else contextlib.nullcontext()
    with cm:
        # a torn/failed transfer re-lifts from the already-gathered host
        # windows: the retry never re-draws, so the sampling stream is
        # unaffected by transfer flakes
        batch = with_retries(lift, "tiered.stage_h2d")
    return StagedChunk(
        batch=batch,
        idxes=sw.idxes,
        old_ptr=sw.old_ptr,
        old_advances=sw.old_advances,
        env_steps=sw.env_steps,
        rng_state=pre_state,
    )


class TieredPrefetchPipeline:
    """Double-buffered staging: a daemon thread stages chunk k+1 (host
    gather + async device_put) while the consumer's fused K-update scan
    executes chunk k.

    depth=1 (the default) is the double buffer: one chunk ready in the
    queue + one being consumed; the thread starts gathering the next only
    after the queued one is taken, so steady-state HBM holds two staging
    slabs — and the consumed slab's buffers are donated back by
    make_stacked_batch_train_step, which is what makes the pair a ring
    rather than a leak. The bounded queue IS the backpressure: a slow
    consumer (compiling, checkpointing) simply stalls staging; a slow
    stager surfaces as TransferTimer wait time (overlap fraction < 1).

    A crash on the staging thread (malformed store, OOM) is re-raised from
    get() instead of starving the consumer silently."""

    def __init__(self, replay: TieredReplayBuffer, rng: np.random.Generator,
                 k: int, timer=None, depth: int = 1):
        self.replay = replay
        self.rng = rng
        self.k = k
        self.timer = timer
        self.q: "queue.Queue[StagedChunk]" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._err: Optional[BaseException] = None
        # RNG state before the draw of a chunk staged but NOT yet queued —
        # the rewind point when stop(rewind=True) catches a stage in flight
        self._inflight_state: Optional[dict] = None
        self._thread = threading.Thread(
            target=self._run, name="tiered-stage", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                if not self.replay.can_sample():
                    # constructed pre-warmup (bench convenience): idle until
                    # the sampling gate opens instead of crashing on an
                    # all-zero tree
                    time.sleep(0.01)
                    continue
                self._inflight_state = self.rng.bit_generator.state
                chunk = stage_chunk(self.replay, self.rng, self.k, self.timer)
                while not self._stop.is_set():
                    try:
                        self.q.put(chunk, timeout=0.1)
                        self._inflight_state = None
                        break
                    except queue.Full:
                        pass
        except BaseException as e:  # noqa: BLE001 — re-raised from get()
            self._err = e

    def get(self) -> StagedChunk:
        """Next staged chunk; the block time (the un-hidden part of the
        host->HBM copy) is recorded as TransferTimer wait."""
        cm = self.timer.wait() if self.timer is not None else contextlib.nullcontext()
        with cm:
            while True:
                if self._err is not None:
                    raise RuntimeError("tiered staging thread died") from self._err
                try:
                    return self.q.get(timeout=0.5)
                except queue.Empty:
                    if not self._thread.is_alive() and self._err is None:
                        raise RuntimeError("tiered staging thread exited")

    def stop(self, rewind: bool = False) -> None:
        """Stop the staging thread. With rewind=True (the preemption path),
        also rewind the sampling RNG to the state before the EARLIEST
        unconsumed draw — queued chunks are discarded, and a resumed run
        re-draws them identically, keeping the sampling stream bit-exact
        across the preempt instead of skipping the prefetched batches."""
        self._stop.set()
        self._thread.join(timeout=10.0)
        if not rewind:
            return
        states = []
        while True:  # drain in FIFO (= draw) order
            try:
                states.append(self.q.get_nowait().rng_state)
            except queue.Empty:
                break
        states.append(self._inflight_state)
        for st in states:
            if st is not None:
                self.rng.bit_generator.state = st
                break
