"""dp-sharded device-resident replay: HBM capacity scales with the mesh.

The single-chip DeviceReplayBuffer (replay/device_store.py) caps replay at
one chip's HBM (~2M transitions of 84x84 obs fills 16 GB). This variant
shards every store's block axis over the mesh's dp axis, so a v4-8 holds
dp x that — the reference's full 2e6-transition capacity
(reference config.py:16) fits in HBM on a 4-way mesh with room to spare.

Design (mirrors the scaling-book recipe: pick a mesh, annotate shardings,
let collectives ride ICI):

- CONTROL PLANE: one host-side ReplayControlPlane PER SHARD (sum tree over
  that shard's sequence slots, its own circular pointer + staleness
  window). Blocks round-robin across shards, so every shard stays
  statistically identical to a 1/dp-sized uniform slice of the stream.
- DATA PLANE: one global jnp array per field with the block axis sharded
  NamedSharding(mesh, P("dp")). A block write is a donated
  dynamic_update_index_in_dim at the owning shard's global slot — XLA
  resolves it to a local update on the owning device.
- SAMPLING: each shard draws batch_size/dp sequences from its own tree;
  IS weights are renormalized across shards to the BATCH-global minimum
  priority, so weights match what a single global tree would produce for
  the same draws (min is over the sampled batch, replay/sum_tree.py).
- TRAINING: learner.make_sharded_fused_multi_train_step runs under
  shard_map — each device gathers its sub-batches from its LOCAL shard (zero
  cross-device data-plane traffic) and gradients psum over dp.

Priority round trip: update_priorities applies each shard's slice under
that shard's own pointer-window staleness mask (reference worker.py:290-307
invariant, per shard).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from r2d2_tpu.config import R2D2Config
from r2d2_tpu.replay.block import Block, store_field_specs
from r2d2_tpu.replay.control_plane import ReplayControlPlane, shard_config
from r2d2_tpu.replay.device_store import DeviceReplayBuffer


@dataclasses.dataclass
class ShardedSampleIdx:
    """Per-shard stacked sample coordinates (host side)."""

    b: np.ndarray           # (dp, B/dp) block slot LOCAL to each shard
    s: np.ndarray           # (dp, B/dp) sequence-in-block
    is_weights: np.ndarray  # (dp, B/dp) float32, batch-globally normalized
    idxes: np.ndarray       # (dp, B/dp) sequence slots LOCAL to each shard
    old_ptr: List[int]       # per-shard block pointer at sample time
    old_advances: List[int]  # per-shard ptr_advances stamp (lap detection)
    env_steps: int


class _ShardTreeMirror:
    """The per-shard face of the parent's stacked device tree: quacks like
    DeviceSumTree for the slice of its API the shard control plane
    (_tree_write) and snapshots (leaves/load_leaves) touch, routing every
    operation to the parent's (dp, tree_size) P("dp")-sharded array — one
    global array, row updates resolved to the owning device by XLA, same
    pattern as the block stores."""

    def __init__(self, parent: "ShardedDeviceReplay", sid: int):
        self.parent = parent
        self.sid = sid

    def update(self, idxes: np.ndarray, td_errors: np.ndarray) -> None:
        if len(idxes) == 0:
            return
        self.parent._dtree_row_update(self.sid, idxes, td_errors)

    def leaves(self) -> np.ndarray:
        p = self.parent
        off = 2 ** (p._dtree_layers - 1) - 1
        return np.asarray(p.dtree_stack[self.sid, off : off + p._dtree_cap])

    def load_leaves(self, values: np.ndarray) -> None:
        self.parent._dtree_row_load(self.sid, values)


class ShardedDeviceReplay:
    def __init__(self, cfg: R2D2Config, mesh: Mesh):
        dp = mesh.shape["dp"]
        if cfg.num_blocks % dp != 0:
            raise ValueError(f"num_blocks {cfg.num_blocks} not divisible by dp {dp}")
        if cfg.batch_size % dp != 0:
            raise ValueError(f"batch_size {cfg.batch_size} not divisible by dp {dp}")
        self.cfg = cfg
        self.mesh = mesh
        self.dp = dp
        self.blocks_per_shard = cfg.num_blocks // dp
        # per-shard view: 1/dp of capacity and batch; the shard config is
        # single-plane (its own control plane knows nothing of the mesh)
        shard_cfg = shard_config(cfg, dp)
        self.shards = [ReplayControlPlane(shard_cfg) for _ in range(dp)]
        self._rr = 0  # round-robin write cursor over shards

        nb = cfg.num_blocks
        shd = NamedSharding(mesh, P("dp"))
        self.stores: Dict[str, jnp.ndarray] = {
            k: jnp.zeros((nb, *shape), dt, device=shd)
            for k, (shape, dt) in store_field_specs(cfg).items()
        }

        def _write(stores, ptr, vals):
            return {
                k: jax.lax.dynamic_update_index_in_dim(arr, vals[k], ptr, axis=0)
                for k, arr in stores.items()
            }

        self._write = jax.jit(
            _write,
            donate_argnums=(0,),
            out_shardings={k: shd for k in self.stores},
        )

        # batched slab write for the on-device collector: the batch deals
        # round-robin starting at shard 0, so shard sid receives blocks
        # sid, sid+dp, ... as ONE contiguous slab in its own region. The
        # write runs under shard_map: each device applies a plain
        # dynamic_update_slice to its LOCAL (nb/dp, ...) store block at its
        # own start offset — no collectives, no GSPMD partitioning of a
        # sharded-axis update (which compiles/executes pathologically; a
        # dynamic-index scatter is just as bad, see
        # DeviceReplayBuffer._write_slab). vals must carry E % dp == 0
        # blocks (add_blocks_batch routes remainders through the
        # single-slot _write); starts: (dp,) LOCAL first slot per shard.
        from r2d2_tpu.parallel.jax_compat import shard_map

        def _slab_body(stores, starts, vals):
            # local views: stores (nb/dp, ...), starts (1,), vals (1, E/dp, ...)
            return {
                k: jax.lax.dynamic_update_slice_in_dim(
                    arr, vals[k][0], starts[0], axis=0
                )
                for k, arr in stores.items()
            }

        def _write_slabs(stores, starts, rr, vals):
            E = next(iter(vals.values())).shape[0]
            # block i -> shard (rr + i) % dp at consecutive local slots:
            # regroup (E, ...) as (dp, E/dp, ...) with [sid, j] = v[j*dp+sid]
            # for rr == 0, then roll the shard axis by the round-robin
            # cursor so the dealing continues where the last add stopped
            grouped = {
                k: jnp.roll(
                    jnp.swapaxes(v.reshape(E // dp, dp, *v.shape[1:]), 0, 1),
                    rr,
                    axis=0,
                )
                for k, v in vals.items()
            }
            specs = {k: P("dp") for k in stores}
            return shard_map(
                _slab_body,
                mesh=mesh,
                in_specs=(specs, P("dp"), {k: P("dp") for k in grouped}),
                out_specs=specs,
                check_vma=False,
            )(stores, starts, grouped)

        self._write_slabs = jax.jit(
            _write_slabs,
            donate_argnums=(0,),
            out_shardings={k: shd for k in self.stores},
        )

        # priority_plane="device": per-shard float32 trees stacked
        # (dp, tree_size) with the SAME P("dp") sharding as the stores —
        # each shard's tree lives next to its blocks. Host-side ingestion
        # mirrors through _ShardTreeMirror row updates; the sharded
        # superstep (megastep.make_sharded_priority_superstep) carries the
        # whole stack through its scan and hands it back via superstep_run.
        self.dtree_stack: Optional[jnp.ndarray] = None
        if cfg.priority_plane == "device":
            from r2d2_tpu.replay import device_sum_tree as dst

            self._dst = dst
            self._dtree_cap = shard_cfg.num_sequences
            self._dtree_layers = dst.tree_layers(self._dtree_cap)
            self._dtree_shd = shd
            tsize = dst.tree_size(self._dtree_layers)
            self.dtree_stack = jnp.zeros((dp, tsize), jnp.float32, device=shd)

            def _row_update(stack, sid, idxes, td):
                row = dst.tree_update(
                    stack[sid], self._dtree_layers, idxes, td, cfg.prio_exponent
                )
                return jax.lax.dynamic_update_index_in_dim(stack, row, sid, axis=0)

            self._row_update_fn = jax.jit(
                _row_update, donate_argnums=(0,), out_shardings=shd
            )
            for sid, sh in enumerate(self.shards):
                sh.attach_device_tree(_ShardTreeMirror(self, sid))
        self.lock = threading.Lock()

    # r2d2: guarded-by(lock)
    def _dtree_row_update(self, sid: int, idxes, td_errors) -> None:
        # callers (_tree_write via add_block/update_priorities) already hold
        # self.lock; the Lock is non-reentrant, so this must not re-acquire
        self.dtree_stack = self._row_update_fn(
            self.dtree_stack,
            jnp.int32(sid),
            jnp.asarray(np.asarray(idxes, np.int32)),
            jnp.asarray(np.asarray(td_errors, np.float32)),
        )

    def _dtree_row_load(self, sid: int, values: np.ndarray) -> None:
        """Snapshot-restore path: rebuild one shard's tree from raw leaves
        and re-deal the stack (host round trip; restore-time only)."""
        host = np.asarray(self.dtree_stack)
        host[sid] = np.asarray(self._dst.tree_from_leaves(values, self._dtree_cap))
        # restore runs before any worker thread starts (single-threaded
        # phase, snapshot.load_replay)  # r2d2: disable=lock-discipline
        self.dtree_stack = jax.device_put(host, self._dtree_shd)

    def superstep_keys(self, key: jax.Array) -> jax.Array:
        """The superstep's (dp, 2) key data from one dispatch key: an
        independent stream per dp shard (fold_in by shard id), mirroring the
        host plane's per-shard Generators."""
        return jnp.stack([jax.random.fold_in(key, sid) for sid in range(self.dp)])

    def superstep_run(self, fn: Callable):
        """Dispatch an in-jit sharded superstep under ONE buffer-lock hold:
        fn(stores, dtree_stack, num_seq_store (dp, nb/dp)) -> (stack',
        rest). Installing the output stack before the lock releases orders
        every later ingestion mirror write after the superstep on the
        device stream — the same serialization argument as
        DeviceReplayBuffer.superstep_run, per shard."""
        with self.lock:
            nss = jnp.asarray(np.stack([sh.num_seq_store for sh in self.shards]))
            stack_out, rest = fn(self.stores, self.dtree_stack, nss)
            self.dtree_stack = stack_out
            return rest

    # ---------------------------------------------------------------- state

    def __len__(self) -> int:
        return sum(len(s) for s in self.shards)

    @property
    def env_steps(self) -> int:
        return sum(s.env_steps for s in self.shards)

    def can_sample(self) -> bool:
        return (
            len(self) >= self.cfg.learning_starts
            and all(s.tree.total > 0 for s in self.shards)
        )

    def pop_episode_stats(self):
        n = r = 0
        for sh in self.shards:
            ni, ri = sh.pop_episode_stats()
            n += ni
            r += ri
        return n, r

    def episode_totals(self):
        n = r = 0
        for sh in self.shards:
            ni, ri = sh.episode_totals()
            n += ni
            r += ri
        return n, r

    # ------------------------------------------------------------------ add

    def add_block(
        self, block: Block, priorities: np.ndarray, episode_reward: Optional[float]
    ) -> None:
        cfg = self.cfg
        vals = DeviceReplayBuffer.pad_block_fields(cfg, block)
        with self.lock:
            shard_id = self._rr
            shard = self.shards[shard_id]
            with shard.lock:
                # write first, account last (see replay_buffer.add_block)
                global_ptr = shard_id * self.blocks_per_shard + shard.block_ptr
                self.stores = self._write(self.stores, global_ptr, vals)
                shard._account_add(
                    block.num_sequences,
                    int(block.learning_steps.sum()),
                    priorities,
                    episode_reward,
                )
            self._rr = (self._rr + 1) % self.dp

    def add_blocks_batch(
        self,
        fields: Dict[str, jnp.ndarray],
        num_seq: np.ndarray,
        learning_totals: np.ndarray,
        priorities: np.ndarray,
        episode_rewards: np.ndarray,
        dones: np.ndarray,
    ) -> None:
        """Write E collector-packed blocks round-robin across shards
        (collect.DeviceCollector contract, mirroring
        DeviceReplayBuffer.add_blocks_batch). The first floor(E/dp)*dp
        blocks land as one shard_map slab write — each device updates its
        local store region, no collectives; the remainder goes through the
        single-slot write. Fields stay on device end to end; only the
        per-block accounting scalars are host-side. Dealing continues the
        round-robin cursor from the previous add, like E sequential
        add_block calls (pinned by test) — UNTIL a shard's local ring
        wraps: from then on the batched path retires tail slots via
        _reserve_contiguous to keep each slab contiguous, so slot
        placement (and the retired blocks' tree state) deliberately
        diverges from the sequential path, which never retires."""
        E = len(num_seq)
        bps = self.blocks_per_shard
        dp = self.dp
        if E > dp * bps:
            raise ValueError(f"{E} blocks per batch exceeds {dp * bps} slots")
        per = E // dp
        Em = per * dp  # slab-written prefix; blocks Em..E-1 write singly
        with self.lock:
            rr = self._rr  # block i -> shard (rr + i) % dp
            # hold EVERY shard's lock across write + account (ascending
            # order; other paths only ever hold one at a time): a sampler
            # draw between the slab write and the accounting would pair new
            # slot data with the evicted blocks' tree state — add_block's
            # single-shard lock gives the same guarantee
            locks = [sh.lock for sh in self.shards]
            for lk in locks:
                lk.acquire()
            try:
                if Em:
                    # destination slots BEFORE accounting mutates the
                    # pointers (write first, account last — same contract
                    # as add_block)
                    starts = np.asarray(
                        [sh._reserve_contiguous(per) for sh in self.shards],
                        np.int64,
                    )
                    slab_fields = {k: v[:Em] for k, v in fields.items()}
                    self.stores = self._write_slabs(
                        self.stores, jnp.asarray(starts, jnp.int32),
                        jnp.int32(rr), slab_fields,
                    )
                    # block i lands at local slot starts[(rr+i)%dp] + i//dp;
                    # accounting in ascending i matches that order per shard
                    for i in range(Em):
                        self.shards[(rr + i) % dp]._account_add(
                            int(num_seq[i]),
                            int(learning_totals[i]),
                            priorities[i],
                            float(episode_rewards[i]) if dones[i] else None,
                        )
                for j in range(E - Em):
                    i = Em + j
                    sid = (rr + j) % dp  # Em is a multiple of dp
                    shard = self.shards[sid]
                    gptr = sid * bps + shard.block_ptr
                    self.stores = self._write(
                        self.stores, gptr, {k: v[i] for k, v in fields.items()}
                    )
                    shard._account_add(
                        int(num_seq[i]),
                        int(learning_totals[i]),
                        priorities[i],
                        float(episode_rewards[i]) if dones[i] else None,
                    )
                self._rr = (rr + E) % dp
            finally:
                for lk in reversed(locks):
                    lk.release()

    # --------------------------------------------------------------- sample

    def sample_indices(
        self, rng: np.random.Generator, locked: bool = False
    ) -> ShardedSampleIdx:
        """Each shard draws B/dp sequences; IS weights renormalized to the
        batch-global minimum priority so the sharded draw matches the
        single-tree semantics. locked=True: the caller already holds every
        shard's lock (the fused runner's draw-under-reservation path)."""
        import contextlib

        bs, ss, idxs, prios = [], [], [], []
        old_ptrs, old_advances = [], []
        for shard in self.shards:
            with shard.lock if not locked else contextlib.nullcontext():
                b, s, idxes, _w = shard._draw(rng)
                old_ptrs.append(shard.block_ptr)
                old_advances.append(shard.ptr_advances)
                # read priorities under the SAME lock as the draw — an
                # interleaved add_block would rewrite these leaves and the
                # weights would no longer describe the drawn sample
                p = shard.tree.priorities_of(idxes)
            bs.append(b)
            ss.append(s)
            idxs.append(idxes)
            prios.append(p)
        p = np.stack(prios)  # (dp, B/dp) raw tree priorities
        positive = p[p > 0.0]
        min_p = positive.min() if positive.size else 1.0
        w = np.power(np.maximum(p, min_p) / min_p, -self.cfg.is_exponent)
        return ShardedSampleIdx(
            b=np.stack(bs).astype(np.int32),
            s=np.stack(ss).astype(np.int32),
            is_weights=w.astype(np.float32),
            idxes=np.stack(idxs),
            old_ptr=old_ptrs,
            old_advances=old_advances,
            env_steps=self.env_steps,
        )

    # ------------------------------------------------------------ round trip

    def update_priorities(
        self,
        idxes: np.ndarray,
        td_errors: np.ndarray,
        old_ptrs: List[int],
        old_advances: Optional[List[int]] = None,
    ) -> None:
        """idxes/td_errors: (dp, B/dp) as returned by sample/train."""
        advances = old_advances if old_advances is not None else [None] * self.dp
        for shard, idx_row, td_row, old_ptr, old_adv in zip(
            self.shards, idxes, np.asarray(td_errors), old_ptrs, advances
        ):
            shard.update_priorities(idx_row, td_row, old_ptr, old_adv)

    def sample_and_run(self, rng: np.random.Generator, k: int, fn: Callable):
        """Draw k per-shard coordinate sets and dispatch fn(stores, draws)
        under ONE buffer-lock hold (multi-update path,
        learner.make_sharded_fused_multi_train_step) — the sharded
        counterpart of DeviceReplayBuffer.sample_and_run. Holding
        self.lock excludes add paths (they take it first), so the in-jit
        gathers read exactly the data the coordinates were drawn
        against."""
        with self.lock:
            draws = [self.sample_indices(rng) for _ in range(k)]
            return draws, fn(self.stores, draws)

    # ------------------------------------------------------------- dispatch

    def run_with_stores(self, fn: Callable):
        """Dispatch fn(stores) under the buffer lock (same contract as
        DeviceReplayBuffer.run_with_stores: the donated write invalidates
        prior store references)."""
        with self.lock:
            return fn(self.stores)
