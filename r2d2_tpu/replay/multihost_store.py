"""Multi-host dp-sharded replay: per-host local stores, one global program.

Extends the single-host sharded plane (replay/sharded_store.py) across
processes, replacing the reference's nothing (it is single-host by
construction, SURVEY.md section 5.8) with the standard JAX multi-host
architecture — per-host ASYNC data planes + one SYNCHRONOUS SPMD learner:

- each host owns the control planes (sum trees, pointers, episode stats)
  and HBM stores for the dp shards whose devices it hosts
  (parallel/multihost.local_axis_indices); its collectors write blocks
  round-robin into those LOCAL shards only. No replay bytes ever cross
  hosts.
- the train step is the SAME shard_map step as single-host
  (learner.make_sharded_fused_multi_train_step over the global mesh). Every
  process calls it in lockstep — standard SPMD — passing global array
  VIEWS assembled zero-copy from the per-host buffers with
  jax.make_array_from_single_device_arrays. Gradient psum rides ICI
  within a host and DCN between hosts, inserted by XLA.
- sampled coordinates are drawn host-locally per shard and assembled the
  same way; priorities come back (dp, B/dp) dp-sharded, and each host
  applies only its addressable rows to its own trees under each shard's
  own staleness window.

Sampling gates host-locally (every shard needs learning_starts/dp
transitions) so no control-plane traffic crosses hosts either; hosts stay
in lockstep purely through the collective train step, exactly like any
SPMD data-parallel trainer.

Current scope: tp=1 (tensor parallelism composes with multi-host at the
mesh level but splits a shard's store across devices; single-host tp>1 is
covered by ShardedDeviceReplay). IS-weight normalization is EXACT
single-tree semantics: hosts ship raw sampled priorities and the train
step finds the batch-global minimum with a pmin collective over dp
(learner.make_sharded_fused_multi_train_step(is_from_priorities=True)) —
the device mesh does the one piece of global coordination the weights need.

Verified end to end by tests/test_multihost.py: a REAL 2-process CPU run
(jax.distributed) trains 3 K=1 PLUS two K=2 run_step_k dispatches
(deferred drain included, global tree mass folded into the checksum)
whose losses match the single-process 4-device run of this plane exactly;
the assembled data plane matches ShardedDeviceReplay loss-for-loss on
identical contents and coordinates; and one K-scan dispatch is pinned
update-for-update against K sequential K=1 dispatches on the same
pre-drawn coordinates.
"""

from __future__ import annotations

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
from r2d2_tpu.parallel.multihost import local_axis_indices


class MultiHostShardedReplay:
    def __init__(self, cfg: R2D2Config, mesh: Mesh, seed: int = 0):
        if mesh.shape.get("tp", 1) != 1:
            raise ValueError("MultiHostShardedReplay supports tp=1 meshes")
        dp = mesh.shape["dp"]
        if cfg.num_blocks % dp != 0 or cfg.batch_size % dp != 0:
            raise ValueError("num_blocks and batch_size must divide over dp")
        self.cfg = cfg
        self.mesh = mesh
        self.dp = dp
        self.blocks_per_shard = cfg.num_blocks // dp
        self.local_ids: List[int] = local_axis_indices(mesh, "dp")
        if not self.local_ids:
            raise ValueError("this process owns no dp shards")
        self.shard_cfg = shard_config(cfg, dp)
        self.shards: Dict[int, ReplayControlPlane] = {
            g: ReplayControlPlane(self.shard_cfg) for g in self.local_ids
        }
        axis = list(mesh.axis_names).index("dp")
        self._shard_device = {
            g: np.take(mesh.devices, g, axis=axis).ravel()[0] for g in self.local_ids
        }
        # fixed for the life of the store; hot paths (install_global_stores,
        # drain_pending) map output shards back by device
        self._dev_to_g = {d: g for g, d in self._shard_device.items()}

        specs = store_field_specs(cfg)
        nbs = self.blocks_per_shard
        self._global_field_shape = {
            k: (cfg.num_blocks, *shape) for k, (shape, _) in specs.items()
        }
        # per-local-shard single-device stores
        self.stores: Dict[int, Dict[str, jnp.ndarray]] = {
            g: {
                k: jax.device_put(np.zeros((nbs, *shape), dt), self._shard_device[g])
                for k, (shape, dt) in specs.items()
            }
            for g in self.local_ids
        }

        def _write(stores, ptr, vals):
            return {
                k: jax.lax.dynamic_update_index_in_dim(arr, vals[k], ptr, axis=0)
                for k, arr in stores.items()
            }

        self._write = jax.jit(_write, donate_argnums=(0,))
        self._rr = 0  # round-robin over LOCAL shards
        self._seed = seed
        self._epoch = 0  # draw counter (part of the draw seeds)
        self._pending = None  # run_step_k's deferred (priorities, draws)
        # store-level lock: add_block's donated write swaps stores[g], so a
        # concurrent run_step_k must not be assembling/dispatching over the
        # old buffers (same contract as run_with_stores on the other device
        # planes). Lock order is ALWAYS self.lock -> shard.lock.
        self.lock = threading.Lock()

    # ---------------------------------------------------------------- state

    def __len__(self) -> int:
        """Transitions stored on THIS host (local shards only)."""
        return sum(len(s) for s in self.shards.values())

    @property
    def env_steps(self) -> int:
        return sum(s.env_steps for s in self.shards.values())

    def can_sample(self) -> bool:
        """Host-local gate: every local shard can serve its sub-batch.
        With symmetric collection across hosts this opens within one block
        of the global gate, with zero cross-host control traffic."""
        return all(
            len(s) >= self.shard_cfg.learning_starts and s.tree.total > 0
            for s in self.shards.values()
        )

    def pop_episode_stats(self):
        n = r = 0
        for sh in self.shards.values():
            ni, ri = sh.pop_episode_stats()
            n += ni
            r += ri
        return n, r

    def episode_totals(self):
        n = r = 0
        for sh in self.shards.values():
            ni, ri = sh.episode_totals()
            n += ni
            r += ri
        return n, r

    # ------------------------------------------------------------------ add

    def _reserve_shards(self, n: int) -> List[int]:
        """Round-robin shard assignment for the next n blocks. The only
        touch of self._rr, so callers can stage each block's H2D copy onto
        its shard device BEFORE taking the store lock — a concurrent
        run_step_k must never wait on a device transfer."""
        with self.lock:
            out = []
            for _ in range(n):
                out.append(self.local_ids[self._rr])
                self._rr = (self._rr + 1) % len(self.local_ids)
            return out

    def _add_one_locked(
        self, g: int, vals: Dict[str, jnp.ndarray], num_sequences: int,
        learning_total: int, priorities: np.ndarray,
        episode_reward: Optional[float],
    ) -> None:
        """Write ONE block's fields into local shard g and account it
        (write first, account last — the add contract shared with the
        other planes). Caller holds self.lock and has already placed vals
        on shard g's device."""
        shard = self.shards[g]
        with shard.lock:
            self.stores[g] = self._write(self.stores[g], shard.block_ptr, vals)
            shard._account_add(
                num_sequences, learning_total, priorities, episode_reward
            )

    def add_block(
        self, block: Block, priorities: np.ndarray, episode_reward: Optional[float]
    ) -> None:
        """Write one block into the next LOCAL shard (host-local op; other
        hosts add to their own shards independently)."""
        vals = DeviceReplayBuffer.pad_block_fields(self.cfg, block)
        (g,) = self._reserve_shards(1)
        vals = {k: jax.device_put(v, self._shard_device[g]) for k, v in vals.items()}
        with self.lock:
            self._add_one_locked(
                g, vals, block.num_sequences, int(block.learning_steps.sum()),
                priorities, episode_reward,
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
        """Write E collector-packed blocks round-robin across this host's
        LOCAL shards (the DeviceCollector contract, mirroring
        ShardedDeviceReplay.add_blocks_batch): collection is host-local,
        so the device collector composes with the multihost plane exactly
        like with the single-host planes. Block i's fields hop from the
        collect dispatch's device to the owning shard's device (an
        intra-host copy of ~one block, staged before the store lock)."""
        gs = self._reserve_shards(len(num_seq))
        staged = [
            {
                k: jax.device_put(v[i], self._shard_device[g])
                for k, v in fields.items()
            }
            for i, g in enumerate(gs)
        ]
        with self.lock:
            for i, g in enumerate(gs):
                self._add_one_locked(
                    g, staged[i],
                    int(num_seq[i]),
                    int(learning_totals[i]),
                    priorities[i],
                    float(episode_rewards[i]) if dones[i] else None,
                )

    # --------------------------------------------------------------- global

    def _assemble(self, per_shard: Dict[int, jnp.ndarray], global_shape, spec: P):
        """Zero-copy global view over per-host single-device buffers."""
        sharding = NamedSharding(self.mesh, spec)
        return jax.make_array_from_single_device_arrays(
            tuple(global_shape), sharding, [per_shard[g] for g in self.local_ids]
        )

    def global_stores(self) -> Dict[str, jnp.ndarray]:
        return {
            k: self._assemble(
                {g: self.stores[g][k] for g in self.local_ids},
                self._global_field_shape[k],
                P("dp"),
            )
            for k in self._global_field_shape
        }

    def install_global_stores(self, new_stores: Dict[str, jnp.ndarray]) -> None:
        """Re-point the per-shard store buffers at a dispatch's returned
        global arrays (the multihost fused megastep donates the old
        buffers and hands back P('dp')-sharded replacements): each host
        keeps only its addressable pieces — zero-copy single-device
        views. Caller holds self.lock."""
        dev_to_g = self._dev_to_g
        fresh: Dict[int, Dict[str, jnp.ndarray]] = {g: {} for g in self.local_ids}
        for k, arr in new_stores.items():
            for piece in arr.addressable_shards:
                fresh[dev_to_g[piece.device]][k] = piece.data
        for g in self.local_ids:
            self.stores[g] = fresh[g]

    # ------------------------------------------------------------- dispatch

    def sample_global_k(self, k: int):
        """K independent global draws stacked for one K-scan dispatch
        (learner.make_sharded_fused_multi_train_step(is_from_priorities=
        True)): B/dp sequences per LOCAL shard and draw, assembled into
        the global (K, dp, B/dp) coordinate arrays of the shard_map step.

        Each shard's draw stream is seeded by (seed, GLOBAL shard id,
        epoch), one epoch per draw — host-layout-independent, so the same
        seeds produce the same global sample whether the shards live on one
        process or many (pinned by the 2-process test), and a K-dispatch
        samples the same coordinate sequence as K dispatches of one from
        the same tree state.

        Returns ((b, s, w) global arrays of shape (K, dp, B/dp), with b
        LOCAL to each shard and w carrying RAW priorities: IS weights are
        computed IN the train step against the batch-global minimum via a
        pmin collective over dp — exact single-tree semantics,
        layout-independent, no cross-host control traffic; plus a list of
        K host-side draw records {idxes, old_ptrs, old_advances}, each
        keyed by local shard, for the deferred priority drain). Caller
        holds self.lock."""
        Bs = self.cfg.batch_size // self.dp
        epoch0 = self._epoch
        self._epoch += k
        draws = [
            {"idxes": {}, "old_ptrs": {}, "old_advances": {}} for _ in range(k)
        ]
        per_b, per_s, per_w = {}, {}, {}
        for g in self.local_ids:
            shard = self.shards[g]
            bk = np.empty((k, 1, Bs), np.int32)
            sk = np.empty((k, 1, Bs), np.int32)
            wk = np.empty((k, 1, Bs), np.float32)
            with shard.lock:
                for i in range(k):
                    rng = np.random.default_rng((self._seed, g, epoch0 + i))
                    b, s, idxes, _w = shard._draw(rng)
                    bk[i, 0], sk[i, 0] = b, s
                    wk[i, 0] = shard.tree.priorities_of(idxes)
                    draws[i]["idxes"][g] = idxes
                    draws[i]["old_ptrs"][g] = shard.block_ptr
                    draws[i]["old_advances"][g] = shard.ptr_advances
            dev = self._shard_device[g]
            per_b[g] = jax.device_put(bk, dev)
            per_s[g] = jax.device_put(sk, dev)
            per_w[g] = jax.device_put(wk, dev)
        shape = (k, self.dp, Bs)
        spec = P(None, "dp")
        return (
            self._assemble(per_b, shape, spec),
            self._assemble(per_s, shape, spec),
            self._assemble(per_w, shape, spec),
        ), draws

    def run_step_k(self, multi_fn: Callable, state, k: int):
        """The plane's ONE dispatch, for every k >= 1: sample locally,
        assemble global views, run k collective updates in one shard_map
        dispatch, with the priority readback DEFERRED one dispatch — the
        multihost form of the device/sharded planes' update. Reading this
        dispatch's (K, dp, B/dp) priorities synchronously would stall
        every host for the dispatch plus a device->host round trip per
        update burst (the >10x cliff ARCHITECTURE.md measures at 2.3 ms
        dispatch / 131 ms readback); instead the transfer starts async and
        the PREVIOUS dispatch's priorities are applied while this one
        executes. Tree priorities lag K extra updates — same bounded class
        as the single-host planes; each shard's pointer-window + lap stamp
        still reject rows overwritten meanwhile.

        multi_fn: make_sharded_fused_multi_train_step(cfg, net, mesh, k,
        is_from_priorities=True). EVERY process calls this in the same
        order (SPMD); the drain itself is host-local."""
        with self.lock:
            # sample + assemble + dispatch under the store lock: a
            # concurrent add_block's donated swap must not invalidate the
            # buffers behind the global views mid-dispatch
            (b, s, w), draws = self.sample_global_k(k)
            new_state, metrics, priorities = multi_fn(
                state, self.global_stores(), b, s, w
            )
        priorities.copy_to_host_async()
        prev, self._pending = self._pending, (priorities, draws)
        if prev is not None:
            self.drain_pending(prev)
        return new_state, metrics

    def drain_pending(self, pending=None) -> None:
        """Apply a deferred (priorities, draws) pair: each host reads only
        its addressable (K, 1, B/dp) pieces and applies row i under draw
        i's own per-shard staleness window AND lap stamp (a full ring lap
        between draw and apply wraps the pointer back into the window
        mask's blind spot — the stamp is the only guard,
        control_plane.update_priorities). Called with the
        previous dispatch's pair each run_step_k, and once with the final
        in-flight pair when the run mode exits (Trainer.finish_updates)."""
        if pending is None:
            pending, self._pending = self._pending, None
        if pending is None:
            return
        prios, draws = pending
        dev_to_g = self._dev_to_g
        for piece in prios.addressable_shards:
            g = dev_to_g[piece.device]
            data = np.asarray(piece.data)  # (K, 1, B/dp)
            for i, d in enumerate(draws):
                self.shards[g].update_priorities(
                    d["idxes"][g], data[i, 0], d["old_ptrs"][g], d["old_advances"][g]
                )
