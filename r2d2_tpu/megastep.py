"""Fused actor-learner megastep: collection + K updates in ONE dispatch.

The threaded full-system mode time-shares the chip between two dispatch
streams (collector chunks and K-update learner chunks) driven by two host
threads. On a single chip those dispatches serialize on the device anyway,
so the threads buy no overlap — they only add dispatch gaps, lock handoffs,
and GIL contention between the streams (measured: the concurrent system
sustained ~29% of the isolated learner rate while collection used ~12% of
the device).

The TPU-native fix is to stop round-tripping the host between the two
phases: ONE jitted dispatch runs

    K prioritized double-Q updates   (gathered in-jit from the HBM replay)
  + one full collection chunk        (policy + env dynamics + block packing,
                                      collect.make_collect_core)
  + the scatter of the E new blocks into the replay store

and the host's only per-dispatch work is sum-tree bookkeeping over a few
kilobytes of coordinates and priorities. XLA's SSA semantics give the
ordering for free: the update gathers read the store argument's PRE-scatter
contents (they were drawn against the host tree's current state), and the
donated scatter reuses the same HBM afterwards.

Semantics vs the threaded system mode (both reference-faithful):
- The chunk is collected with the params at dispatch entry (pre-update).
  The reference's actors run on weights up to publish_interval x
  actor_update_interval steps stale (reference worker.py:744-751); here the
  collection policy is at most K updates stale — strictly fresher — and no
  param publish transfer is needed at all for collection.
- New blocks enter the tree only after the dispatch returns, so updates
  within a dispatch never sample the chunk being collected alongside them —
  same one-chunk lag class as the threaded mode's queue depths (reference
  worker.py:364-371 tolerates ~12 batches).
- Priorities computed by the K updates land on the tree AFTER the chunk's
  blocks are accounted, so the pointer-window staleness mask (reference
  worker.py:290-307 invariant) rejects exactly the rows the scatter
  overwrote.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from r2d2_tpu.config import R2D2Config
from r2d2_tpu.collect import default_chunk_len, make_collect_core
from r2d2_tpu.learner import TrainState, make_multi_update_core
from r2d2_tpu.models.r2d2 import R2D2Network
from r2d2_tpu.utils.profiling import SPANS, counted, put, register_program, scoped, span


def _reserve(rings, slots: int) -> list:
    """Each ring's first reserved slot (callers hold the rings' locks).
    Reserve BEFORE drawing: it retires the slots' old blocks and advances
    the ring pointer, so the draws that follow can neither target the
    in-flight chunk's slots nor produce priority rows the staleness mask
    would miss."""
    with span("r2d2.replay.reserve", slots=slots):
        return [ring._reserve_advance(slots) for ring in rings]


def _stack_coordinates(draws, starts=None):
    """K host draws -> the (K, ...) b / s / w device arrays of one dispatch,
    and the reserved slot start(s) as a device array (None without a chunk)."""
    with span("r2d2.dispatch.upload"):
        return (
            jnp.asarray(np.stack([d.b for d in draws])),
            jnp.asarray(np.stack([d.s for d in draws])),
            jnp.asarray(np.stack([d.is_weights for d in draws])),
            None if starts is None else jnp.asarray(starts, jnp.int32),
        )


def _priorities_span() -> span:
    """The span over one dispatch's priority rows, stamped with the rows
    offered and applied SO FAR: a traced window's share is its last span's
    stamps less its first's (the counters themselves run from process start)."""
    return span(
        "r2d2.replay.priorities",
        offered=int(counted("replay.priority_rows_offered")),
        applied=int(counted("replay.priority_rows_applied")),
    )


def _start_async_copy(arrs) -> None:
    """Kick off device->host transfers for a pytree of arrays; collected
    later while subsequent dispatches execute."""
    for arr in jax.tree.leaves(arrs):
        arr.copy_to_host_async()


def _slab_write(stores, fields, start):
    """The E new blocks into E CONTIGUOUS slots of every store field."""
    return {
        k: jax.lax.dynamic_update_slice_in_dim(arr, fields[k], start, axis=0)
        for k, arr in stores.items()
    }


def make_megastep(
    cfg: R2D2Config,
    net: R2D2Network,
    fn_env,
    num_envs: int,
    chunk_len: int,
    num_updates: int,
    donate: bool = True,
):
    """Build the fused dispatch.

    Signature:
      mega(state, stores, env_state, epsilons, key, b, s, w, ptr0) ->
        (state', stores', metrics, priorities (K, B),
         (chunk_prios, num_seq, sizes, dones, ep_rewards), env_state', key')

    b/s/w are (K, B) stacked sample coordinates drawn by the host against
    the current tree; ptr0 is the first of the E CONTIGUOUS store slots the
    host reserved for the chunk's blocks (ReplayControlPlane.
    _reserve_contiguous — a contiguous slab write runs at memcpy speed
    where a ring-crossing scatter costs seconds on TPU). Exactly
    equivalent to running learner.make_fused_multi_train_step on the same
    coordinates followed by collect + DeviceReplayBuffer.add_blocks_batch
    with the same key (pinned by tests/test_megastep.py)."""
    collect_core = scoped(
        make_collect_core(cfg, net, fn_env, num_envs, chunk_len), "r2d2_collect"
    )
    multi_core = make_multi_update_core(cfg, net, num_updates)  # scope r2d2_update
    slab_write = scoped(_slab_write, "r2d2_slab_write")

    def mega(state: TrainState, stores, env_state, epsilons, key, b, s, w, ptr0):
        # collection uses the dispatch-entry params: the freshest policy any
        # actor design could see without re-publishing mid-dispatch
        act_params = state.params
        state, metrics, priorities = multi_core(state, stores, b, s, w)

        (fields, chunk_prios, num_seq, sizes, dones, ep_rewards, fresh_env, key2) = (
            collect_core(act_params, env_state, epsilons, key)
        )
        new_stores = slab_write(stores, fields, ptr0)
        return (
            state,
            new_stores,
            metrics,
            priorities,
            (chunk_prios, num_seq, sizes, dones, ep_rewards),
            fresh_env,
            key2,
        )

    return jax.jit(mega, donate_argnums=(0, 1) if donate else ())


class _DeferredDrainRunner:
    """The deferred-drain dispatch protocol, defined ONCE for both the
    single-chip and multi-chip fused runners (subclasses supply the
    plane-specific pieces): samples_per_insert pacing on actual
    consumed:inserted counters, the pending-readback rotation (priorities
    AND chunk bookkeeping collected one dispatch late), the aliasing
    guard, and finish(). Subclasses implement

      _dispatch(state, collect) -> (state', metrics, priorities, draws,
                                    token, chunk_host)
        reservation + draws + the jitted call through _launch (which
        starts the async readbacks), under the plane's locks (token
        identifies the reserved slots; chunk_host the bookkeeping
        arrays, both None when collect is False);
      _account_chunk(token, arrays) -> recorded
        install a drained chunk's accounting into the tree(s).
    """

    def _init_protocol(
        self,
        cfg: R2D2Config,
        replay,
        collect_every: int,
        samples_per_insert: float,
        sample_rng,
        chunk_len,
        ring_slots: int,
        ring_envs: int,
    ) -> None:
        """ring_slots/ring_envs: ONE ring's slot count and writer batch
        (the whole store single-chip; one shard's slice multi-chip)."""
        self.cfg = cfg
        self.replay = replay
        self.K = cfg.updates_per_dispatch
        self.chunk = int(chunk_len or default_chunk_len(cfg))
        if cfg.max_episode_steps > self.chunk:
            # the fused collect core runs WITHOUT cross-chunk episode
            # carry (its env_state threads through the dispatch as a bare
            # state): episodes longer than one chunk would silently never
            # visit their tail. The standalone DeviceCollector carries
            # episodes across chunks (collect.CollectCarry) — use the
            # threaded/inline modes for such envs, or size block_length
            # to hold a full episode for the fused mode.
            raise ValueError(
                f"fused megastep: max_episode_steps={cfg.max_episode_steps} "
                f"exceeds the collection chunk ({self.chunk}); episodes "
                "would be truncated at every chunk and their tails never "
                "collected. Size block_length >= max_episode_steps or use "
                "collector='device' with the threaded/inline modes (cross-"
                "chunk episode carry)."
            )
        # deferred-drain aliasing bound: between a draw and its priority
        # application (one dispatch later) at most two chunks can land,
        # each advancing the ring by its E plus a wrap skip of < E. The
        # pointer-window mask is correct for any advancement < ring_slots;
        # a FULL lap would alias ptr == old_ptr and apply stale priorities
        # to fresh blocks, so reject configs where the bound can reach it.
        # The same guard covers the chunk-accounting deferral: a pending
        # chunk's slots could only be re-reserved by the next chunk when
        # ring_slots < 3E (reserve advances at most 2E-1 past the pending
        # slab), and consecutive collects require chunks_between=2 below,
        # i.e. ring_slots >= 4E-1 — strictly stronger.
        chunks_between = 2 if collect_every == 1 or samples_per_insert > 0 else 1
        max_advance = chunks_between * (2 * ring_envs - 1)
        if max_advance >= ring_slots:
            raise ValueError(
                f"store too small for deferred priorities: {ring_slots} "
                f"block slots per ring but up to {max_advance} can be "
                f"overwritten between a draw and its application "
                f"(ring E={ring_envs}); grow buffer_capacity or reduce "
                "num_actors"
            )
        if collect_every < 1:
            raise ValueError("collect_every must be >= 1")
        self.collect_every = collect_every
        # samples_per_insert > 0: ignore the fixed modulo and decide per
        # dispatch from ACTUAL counters (the threaded pacer's rule,
        # train.py actor_body) — chunks are episode-aligned and record
        # fewer than E*chunk_len transitions, so a ratio derived from the
        # theoretical max insert rate would silently overshoot the target.
        # Baseline: THIS-RUN insertions only, off the replay's recorded
        # counter (warmup/snapshot totals must not skew the ratio).
        self.samples_per_insert = samples_per_insert
        self._consumed = 0
        self._inserted0 = replay.env_steps
        self._dispatch_count = 0
        self.total_env_steps = 0
        self._pending = None        # deferred (priorities, draws, core counts) readback
        self._pending_chunk = None  # deferred (token, chunk bookkeeping)
        self.replay_rng = (
            sample_rng if sample_rng is not None else np.random.default_rng(0)
        )

    def step(self, state: TrainState):
        """One dispatch (K updates, plus the chunk on collect dispatches);
        returns (state', metrics, env_steps_recorded). With both readbacks
        deferred, `recorded` reports the PREVIOUS dispatch's chunk as its
        accounting lands (zero on the first collect)."""
        # consumption counted BEFORE the decision: this dispatch's K
        # updates are committed either way, and an understated consumed
        # would skip the first collect for no reason
        self._consumed += self.K * self.cfg.batch_size * self.cfg.learning_steps
        if self.samples_per_insert > 0:
            # chunk accounting is deferred one dispatch, so `inserted` lags
            # one chunk: the first dispatches see ~1 and always collect (a
            # bounded initial burst), and steady-state pacing tracks the
            # target ratio one chunk behind — harmless (the staleness
            # guard assumes consecutive collects), documented here so the
            # early overshoot doesn't read as a pacing bug
            inserted = max(self.replay.env_steps - self._inserted0, 1)
            collect = self._consumed / inserted >= self.samples_per_insert
        else:
            collect = self._dispatch_count % self.collect_every == 0
        self._dispatch_count += 1

        # one host span for the whole dispatch; its children (sample with
        # reserve and draw, launch with upload and call, readback, account,
        # priorities) nest inside it on this thread and share the `dispatch`
        # id through it
        with span("r2d2.dispatch", dispatch=self._dispatch_count, collect=int(collect)):
            state, m, prios, draws, token, chunk_host = self._dispatch(state, collect)

            recorded = 0
            prev_chunk = self._pending_chunk
            self._pending_chunk = (token, chunk_host) if collect else None
            if prev_chunk is not None:
                recorded = self._drain_chunk(prev_chunk)
            # what the core counted in this dispatch's last update (a core
            # that counts: models/hybrid_stack.py) rides with the priorities
            core_counts = {k: v for k, v in m.items() if k in SPANS}
            _start_async_copy(core_counts)
            prev, self._pending = self._pending, (prios, draws, core_counts)
            if prev is not None:
                self._drain(prev)
        return state, m, recorded

    def _launch(self, program, collect: bool, *args):
        """The jitted call, and the start of this dispatch's readbacks
        (async: collected next call, while the next dispatch executes)."""
        with span("r2d2.dispatch.call", program=program.name):
            out = program(*args)
        _start_async_copy((out[3], out[4]) if collect else out[2])
        return out

    def _drain_chunk(self, pending) -> int:
        """Install a deferred chunk's accounting (tree priorities, sizes,
        episode stats) at its reserved slots; returns recorded steps."""
        token, chunk_host = pending
        with span("r2d2.dispatch.readback"):
            arrays = tuple(map(np.asarray, chunk_host))
        with span("r2d2.replay.account"):
            recorded = self._account_chunk(token, arrays)
        self.total_env_steps += recorded
        return recorded

    def _drain(self, pending) -> None:
        prios, draws, core_counts = pending
        with span("r2d2.dispatch.readback"):
            rows = np.asarray(prios)
            core_counts = jax.device_get(core_counts)
        if core_counts:  # the last drained update's readings
            put("moe.rows_offered", core_counts["moe.rows_offered"])
            put("moe.rows_dropped", core_counts["moe.rows_dropped"])
            put("moe.dropped_share", core_counts["moe.dropped_share"])
            put("moe.load_max_over_mean", core_counts["moe.load_max_over_mean"])
        with _priorities_span():
            for row, d in zip(rows, draws):
                # each row under its own draw's staleness window and lap
                # stamp (old_ptr: an int, or one per shard)
                self.replay.update_priorities(d.idxes, row, d.old_ptr, d.old_advances)

    def finish(self) -> int:
        """Apply the final in-flight readbacks (chunk accounting first,
        then priorities); call once when the driving loop stops updating.
        Returns the env steps recorded by the final chunk drain."""
        recorded = 0
        pending_chunk, self._pending_chunk = self._pending_chunk, None
        if pending_chunk is not None:
            recorded = self._drain_chunk(pending_chunk)
        pending, self._pending = self._pending, None
        if pending is not None:
            self._drain(pending)
        return recorded


class FusedSystemRunner(_DeferredDrainRunner):
    """Drives the megastep against a DeviceReplayBuffer + DeviceCollector.

    Owns the per-dispatch protocol (the Trainer's fused mode, and through
    it the benchmark, go through here):

      1. under the replay lock: draw K x B coordinates, reserve the next E
         ring slots, dispatch (donating the stores), install the returned
         stores.
      2. read back the chunk's host-side bookkeeping (a few kB) and account
         the E new blocks — this advances the ring pointer past the
         reserved slots.
      3. apply the K update-priority rows under each draw's own staleness
         window: rows targeting slots the chunk overwrote are rejected by
         the pointer-window mask because accounting ran first.

    BOTH readbacks are DEFERRED one dispatch: reading this dispatch's
    priorities or chunk bookkeeping immediately would stall the host for
    the dispatch's execution plus a device->host round trip. Instead both
    transfers start async and are collected while the NEXT dispatch
    executes, so the host never blocks on the dispatch it just issued.

    What makes chunk deferral safe is reserve-time pointer advancement
    (ReplayControlPlane._reserve_advance): the reserved slots' old blocks
    are retired (leaves zeroed, size deducted) and the ring pointer moves
    past them BEFORE the dispatch and BEFORE any draw — so (a) no draw can
    target a slot whose contents are in flight, and (b) the pointer-window
    staleness mask already rejects any stale priority row aimed at those
    slots. The deferred accounting (_account_blocks_at) then only has to
    install the new blocks' tree priorities and counters; ordering against
    the priority drain no longer matters. Replay availability of a chunk
    lags one extra dispatch — the same lag class as the threaded mode's
    queue depths (reference worker.py:364-371 tolerates ~12 batches).

    `collect_every` dispatches include the collection chunk; the others run
    the plain K-update dispatch (learner.make_fused_multi_train_step) so
    the insert:consume ratio is tunable without recompilation (two compiled
    programs, selected per dispatch)."""

    def __init__(
        self,
        cfg: R2D2Config,
        net: R2D2Network,
        fn_env,
        replay,
        epsilons: jnp.ndarray,
        env_state,
        key: jax.Array,
        collect_every: int = 1,
        chunk_len: Optional[int] = None,
        sample_rng: Optional[np.random.Generator] = None,
        samples_per_insert: float = 0.0,
    ):
        from r2d2_tpu.learner import make_fused_multi_train_step

        self.E = cfg.num_actors
        self._init_protocol(
            cfg, replay, collect_every, samples_per_insert, sample_rng,
            chunk_len, ring_slots=cfg.num_blocks, ring_envs=self.E,
        )
        self.epsilons = epsilons
        self.env_state = env_state
        self.key = key
        self._mega = register_program(
            "mega", make_megastep(cfg, net, fn_env, self.E, self.chunk, self.K)
        )
        self._multi = register_program(
            "multi", make_fused_multi_train_step(cfg, net, self.K)
        )

    def _dispatch(self, state: TrainState, collect: bool):
        replay = self.replay
        ptr0 = chunk_host = None
        with replay.lock:
            with span("r2d2.replay.sample"):
                if collect:
                    (ptr0,) = _reserve([replay], self.E)
                with span("r2d2.replay.draw", k=self.K):
                    draws = [replay._draw_sample_idx(self.replay_rng) for _ in range(self.K)]
            with span("r2d2.dispatch.launch"):
                b, s, w, start = _stack_coordinates(draws, ptr0)
                if collect:
                    (state, new_stores, m, prios, chunk_host, self.env_state, self.key) = (
                        self._launch(
                            self._mega, True,
                            state, replay.stores, self.env_state, self.epsilons,
                            self.key, b, s, w, start,
                        )
                    )
                    replay.stores = new_stores
                else:
                    state, m, prios = self._launch(
                        self._multi, False, state, replay.stores, b, s, w
                    )
        return state, m, prios, draws, ptr0, chunk_host

    def _account_chunk(self, ptr0: int, arrays) -> int:
        chunk_prios, num_seq, sizes, dones, ep_rewards = arrays
        # chunks are episode-aligned: every recorded transition is a
        # learning step (collect.py _pack), so learning totals == sizes
        with self.replay.lock:
            self.replay._account_blocks_at(
                ptr0, num_seq, sizes, chunk_prios, ep_rewards, dones
            )
        return int(sizes.sum())


# ---------------------------------------------------------------------------
# Multi-chip fused megastep: the same single-dispatch system over a dp mesh.
# ---------------------------------------------------------------------------


def make_sharded_megastep(
    cfg: R2D2Config,
    net: R2D2Network,
    fn_env,
    mesh,
    num_envs: int,
    chunk_len: int,
    num_updates: int,
    donate: bool = True,
    is_from_priorities: bool = False,
):
    """The multi-chip megastep: ONE shard_map dispatch over the mesh's dp
    axis runs, PER DEVICE,

      K prioritized double-Q updates gathered from the device's LOCAL
      replay shard (gradients psum over dp — ICI traffic is gradients
      only, the data plane never crosses devices)
    + a full collection chunk over the device's LOCAL E/dp envs (policy +
      env dynamics + block packing, collect.make_collect_core)
    + the slab write of those E/dp blocks into the device's local store
      region (a plain dynamic_update_slice on the local view — the same
      no-collectives trick as ShardedDeviceReplay._write_slabs)

    Env slots are PINNED to their device for the run: shard s always
    collects envs [s*E/dp, (s+1)*E/dp) and writes their blocks to its own
    ring — each shard's stream is a statistically identical 1/dp slice, so
    no round-robin dealing (and no cross-device block traffic) is needed.

    Signature: mega(state, stores, env_state, epsilons, keys, b, s, w,
    starts) -> (state', stores', metrics, priorities (K, dp, B/dp),
    (chunk_prios, num_seq, sizes, dones, ep_rewards) each (E, ...),
    env_state', keys') where b/s/w are (K, dp, B/dp) per-shard LOCAL
    coordinates, keys is a (dp,) key vector (one PRNG stream per shard),
    starts (dp,) the per-shard LOCAL first slot reserved via
    _reserve_advance, and env_state/epsilons are sharded over dp on their
    leading E axis. Ordering semantics are identical to the single-chip
    megastep (SSA: update gathers read pre-scatter store contents).

    is_from_priorities=True: w carries RAW sampled tree priorities,
    normalized per update with a pmin over dp inside the scan
    (make_multi_update_core) — the multihost runner's path, where hosts
    only know their local shards' priorities."""
    from jax.sharding import PartitionSpec as P
    from r2d2_tpu.parallel.jax_compat import shard_map
    from r2d2_tpu.parallel.mesh import dp_manual_axes

    dp = mesh.shape["dp"]
    if num_envs % dp:
        raise ValueError(f"num_envs {num_envs} not divisible by dp {dp}")
    E_local = num_envs // dp
    collect_core = scoped(
        make_collect_core(cfg, net, fn_env, E_local, chunk_len), "r2d2_collect"
    )
    multi_core = make_multi_update_core(  # scope r2d2_update
        cfg, net, num_updates, axis_name="dp",
        is_from_priorities=is_from_priorities,
    )
    slab_write = scoped(_slab_write, "r2d2_slab_write")

    def body(state, stores, env_state, epsilons, keys, b, s, w, starts):
        # local views: stores (nb/dp, ...), env_state/epsilons (E/dp, ...),
        # keys (1,), b/s/w (K, 1, B/dp), starts (1,)
        act_params = state.params
        state, metrics, prios = multi_core(state, stores, b[:, 0], s[:, 0], w[:, 0])
        (fields, chunk_prios, num_seq, sizes, dones, ep_rewards, fresh_env, key2) = (
            collect_core(act_params, env_state, epsilons, keys[0])
        )
        new_stores = slab_write(stores, fields, starts[0])
        return (
            state,
            new_stores,
            metrics,
            prios[:, None],
            (chunk_prios, num_seq, sizes, dones, ep_rewards),
            fresh_env,
            key2[None],
        )

    # P("dp") entries are PREFIX specs: one spec covers every leaf of the
    # stores dict / env-state pytree / bookkeeping tuple.
    # dp_manual_axes: with tp > 1, manual over dp only — the tp axis stays
    # GSPMD-auto, so tp-sharded params (train_state_shardings) partition
    # the update's matmuls inside each dp shard (collection math is
    # tp-replicated: its env/obs operands carry no tp sharding); with
    # tp == 1, fully manual, so the Pallas core can live in the body.
    mega = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(), P("dp"), P("dp"), P("dp"), P("dp"),
            P(None, "dp"), P(None, "dp"), P(None, "dp"), P("dp"),
        ),
        out_specs=(
            P(), P("dp"), P(), P(None, "dp"), P("dp"), P("dp"), P("dp"),
        ),
        axis_names=dp_manual_axes(mesh),
        check_vma=False,
    )
    return jax.jit(mega, donate_argnums=(0, 1) if donate else ())


class ShardedFusedRunner(_DeferredDrainRunner):
    """Drives the sharded megastep against a ShardedDeviceReplay — the
    multi-chip FusedSystemRunner. Same deferred-drain protocol (reserve
    advances every shard's ring before the draws; priority and chunk
    readbacks collected one dispatch later), applied per shard:

      1. under all shard locks: _reserve_advance(E/dp) on every shard,
         then K stacked per-shard coordinate draws, then ONE dispatch.
      2. next call drains the previous dispatch's chunk bookkeeping into
         each shard's tree at its reserved slots, and the previous
         priorities under each shard's own staleness window.
    """

    def __init__(
        self,
        cfg: R2D2Config,
        net: R2D2Network,
        fn_env,
        replay,
        epsilons,
        env_state,
        key: jax.Array,
        mesh,
        collect_every: int = 1,
        chunk_len: Optional[int] = None,
        sample_rng: Optional[np.random.Generator] = None,
        samples_per_insert: float = 0.0,
    ):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from r2d2_tpu.learner import make_sharded_fused_multi_train_step

        self.mesh = mesh
        dp = replay.dp
        self.dp = dp
        E = cfg.num_actors
        if E % dp:
            raise ValueError(f"num_actors {E} not divisible by dp {dp}")
        self.E_local = E // dp
        self._init_protocol(
            cfg, replay, collect_every, samples_per_insert, sample_rng,
            chunk_len, ring_slots=replay.blocks_per_shard, ring_envs=self.E_local,
        )
        shd = NamedSharding(mesh, P("dp"))
        self.epsilons = jax.device_put(jnp.asarray(epsilons, jnp.float32), shd)
        self.env_state = jax.device_put(env_state, shd)
        # one PRNG stream per shard, sharded alongside its envs
        self.keys = jax.device_put(jax.random.split(key, dp), shd)
        self._mega = register_program("mega", make_sharded_megastep(
            cfg, net, fn_env, mesh, E, self.chunk, self.K
        ))
        self._multi = register_program(
            "multi", make_sharded_fused_multi_train_step(cfg, net, mesh, self.K)
        )

    def _dispatch(self, state: TrainState, collect: bool):
        replay = self.replay
        starts = chunk_host = None
        with replay.lock:
            with span("r2d2.replay.sample"):
                locks = [sh.lock for sh in replay.shards]
                for lk in locks:
                    lk.acquire()
                try:
                    if collect:
                        starts = _reserve(replay.shards, self.E_local)
                    with span("r2d2.replay.draw", k=self.K):
                        draws = [
                            replay.sample_indices(self.replay_rng, locked=True)
                            for _ in range(self.K)
                        ]
                finally:
                    for lk in reversed(locks):
                        lk.release()
            with span("r2d2.dispatch.launch"):
                b, s, w, starts_dev = _stack_coordinates(draws, starts)
                if collect:
                    (state, new_stores, m, prios, chunk_host,
                     self.env_state, self.keys) = self._launch(
                        self._mega, True,
                        state, replay.stores, self.env_state, self.epsilons,
                        self.keys, b, s, w, starts_dev,
                    )
                    replay.stores = new_stores
                else:
                    state, m, prios = self._launch(
                        self._multi, False, state, replay.stores, b, s, w
                    )
        return state, m, prios, draws, starts, chunk_host

    def _account_chunk(self, starts, arrays) -> int:
        chunk_prios, num_seq, sizes, dones, ep_rewards = arrays
        El = self.E_local
        recorded = 0
        for sid, shard in enumerate(self.replay.shards):
            sl = slice(sid * El, (sid + 1) * El)
            with shard.lock:
                shard._account_blocks_at(
                    int(starts[sid]), num_seq[sl], sizes[sl],
                    chunk_prios[sl], ep_rewards[sl], dones[sl],
                )
            recorded += int(sizes[sl].sum())
        return recorded


class MultiHostFusedRunner(_DeferredDrainRunner):
    """The fused megastep over a GLOBAL (possibly multi-process) mesh —
    the sharded runner's protocol on MultiHostShardedReplay. Every
    process calls step() in lockstep (the dispatch is SPMD-collective);
    everything host-side is LOCAL:

    - draws come from replay.sample_global_k (per-LOCAL-shard, raw
      priorities -> in-step pmin IS normalization);
    - slot reservation, chunk accounting, and the deferred priority
      drain each touch only this host's shards, read through the global
      arrays' addressable pieces;
    - env slots are pinned per shard (the sharded megastep's rule): this
      host materializes env states and epsilon rows only for its local
      shards, assembled zero-copy into the global (E, ...) views the
      dispatch consumes.

    cfg.num_actors is the GLOBAL env count (E/dp per shard, like
    ShardedFusedRunner). samples_per_insert pacing is converted to a
    deterministic every-n-dispatches cadence at construction: the ratio
    pacer runs on host-local counters, and hosts disagreeing about
    collect on the same step would dispatch mismatched collective
    programs. Validated end to end on the single-process multi-device
    mesh (tests + dryrun phase 6); the host-side plumbing uses only
    addressable-shard APIs so a physical multi-host run has the correct
    per-process structure."""

    def __init__(
        self,
        cfg: R2D2Config,
        net: R2D2Network,
        fn_env,
        replay,
        epsilons,
        key: jax.Array,
        mesh,
        collect_every: int = 1,
        chunk_len: Optional[int] = None,
        sample_rng: Optional[np.random.Generator] = None,
        samples_per_insert: float = 0.0,
    ):
        from jax.sharding import PartitionSpec as P

        from r2d2_tpu.learner import make_sharded_fused_multi_train_step

        self.mesh = mesh
        dp = replay.dp
        self.dp = dp
        E = cfg.num_actors
        if E % dp:
            raise ValueError(f"num_actors {E} not divisible by dp {dp}")
        self.E_local = E // dp
        if samples_per_insert > 0:
            # ratio pacing runs on host-LOCAL insert counters, so on a
            # multi-process mesh different hosts could decide collect
            # differently on the same step and dispatch MISMATCHED
            # collective programs (SPMD deadlock). Convert the target
            # ratio ONCE into a deterministic every-n-dispatches cadence
            # every process computes identically: n = spi * (steps one
            # chunk inserts, upper bound) / (steps K updates consume).
            chunk0 = int(chunk_len or default_chunk_len(cfg))
            consumed = cfg.updates_per_dispatch * cfg.batch_size * cfg.learning_steps
            collect_every = max(1, round(samples_per_insert * E * chunk0 / consumed))
            samples_per_insert = 0.0
        self._init_protocol(
            cfg, replay, collect_every, samples_per_insert, sample_rng,
            chunk_len, ring_slots=replay.blocks_per_shard, ring_envs=self.E_local,
        )
        self._dev_to_g = replay._dev_to_g

        # per-LOCAL-shard env slots, epsilon rows, and PRNG streams,
        # assembled into global views (shard g owns env rows
        # [g*E/dp, (g+1)*E/dp) — the pinned-slot rule)
        eps_np = np.asarray(epsilons, np.float32)
        if len(eps_np) != E:
            raise ValueError(f"epsilons must be the GLOBAL (E={E},) ladder")
        per_eps, per_env, per_key = {}, {}, {}
        for g in replay.local_ids:
            dev = replay._shard_device[g]
            rows = slice(g * self.E_local, (g + 1) * self.E_local)
            per_eps[g] = jax.device_put(eps_np[rows], dev)
            env_g = jax.vmap(fn_env.reset)(
                jax.random.split(jax.random.fold_in(key, g), self.E_local)
            )
            per_env[g] = jax.device_put(env_g, dev)
            per_key[g] = jax.device_put(
                jax.random.fold_in(key, 10_000 + g)[None], dev
            )
        self.epsilons = replay._assemble(per_eps, (E,), P("dp"))
        self.env_state = self._assemble_tree(per_env, E)
        self.keys = self._assemble_tree(per_key, dp)
        self._mega = register_program("mega", make_sharded_megastep(
            cfg, net, fn_env, mesh, E, self.chunk, self.K,
            is_from_priorities=True,
        ))
        self._multi = register_program("multi", make_sharded_fused_multi_train_step(
            cfg, net, mesh, self.K, is_from_priorities=True
        ))

    # ------------------------------------------------------------ helpers

    def _assemble_tree(self, per_g, leading: int):
        """Per-local-shard pytrees (leaves (E/dp, ...) or (1, ...)) ->
        global pytree with every leaf (leading, ...) sharded P('dp')."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        replay = self.replay
        trees = [per_g[g] for g in replay.local_ids]

        def comb(*leaves):
            shape = (leading,) + tuple(leaves[0].shape[1:])
            return jax.make_array_from_single_device_arrays(
                shape, NamedSharding(self.mesh, P("dp")), list(leaves)
            )

        return jax.tree.map(comb, *trees)

    # ----------------------------------------------------------- protocol

    def _dispatch(self, state: TrainState, collect: bool):
        from jax.sharding import PartitionSpec as P

        replay = self.replay
        starts_d = chunk_host = None  # starts_d: {local shard: first reserved slot}
        with replay.lock:
            with span("r2d2.replay.sample"):
                if collect:
                    rings = [replay.shards[g] for g in replay.local_ids]
                    with contextlib.ExitStack() as held:
                        for ring in rings:
                            held.enter_context(ring.lock)
                        starts_d = dict(zip(replay.local_ids, _reserve(rings, self.E_local)))
                # the draws AND their upload (sample_global_k assembles the
                # global coordinate arrays): this plane's draw span holds both
                with span("r2d2.replay.draw", k=self.K):
                    (b, s, w), draws = replay.sample_global_k(self.K)
            with span("r2d2.dispatch.launch"):
                if collect:
                    with span("r2d2.dispatch.upload"):
                        per_start = {
                            g: jax.device_put(
                                # host int -> tiny per-shard upload, once per chunk
                                np.asarray([start], np.int32),  # r2d2: disable=host-sync-in-hot-path
                                replay._shard_device[g],
                            )
                            for g, start in starts_d.items()
                        }
                        starts = replay._assemble(per_start, (self.dp,), P("dp"))
                    (state, new_stores, m, prios, chunk_host,
                     self.env_state, self.keys) = self._launch(
                        self._mega, True,
                        state, replay.global_stores(), self.env_state,
                        self.epsilons, self.keys, b, s, w, starts,
                    )
                    replay.install_global_stores(new_stores)
                else:
                    state, m, prios = self._launch(
                        self._multi, False, state, replay.global_stores(), b, s, w
                    )
        return state, m, prios, draws, starts_d, chunk_host

    def _drain_chunk(self, pending) -> int:
        """Install a deferred chunk's accounting per LOCAL shard, reading
        only the global bookkeeping arrays' addressable pieces (the base
        class's np.asarray would touch non-addressable shards on a
        multi-process mesh)."""
        starts_d, chunk_host = pending
        replay = self.replay
        per_g = {g: [None] * len(chunk_host) for g in replay.local_ids}
        with span("r2d2.dispatch.readback"):
            for fi, field in enumerate(chunk_host):
                for piece in field.addressable_shards:
                    # deliberate readback: tiny accounting arrays, once per chunk
                    per_g[self._dev_to_g[piece.device]][fi] = np.asarray(piece.data)  # r2d2: disable=host-sync-in-hot-path
        recorded = 0
        with span("r2d2.replay.account"):
            for g in replay.local_ids:
                chunk_prios, num_seq, sizes, dones, ep_rewards = per_g[g]
                with replay.shards[g].lock:
                    replay.shards[g]._account_blocks_at(
                        int(starts_d[g]), num_seq, sizes, chunk_prios,
                        ep_rewards, dones,
                    )
                recorded += int(sizes.sum())
        self.total_env_steps += recorded
        return recorded

    def _drain(self, pending) -> None:
        # the store's deferred-drain applier handles an explicit pending
        # pair: addressable pieces only, row i under draw i's per-shard
        # staleness window + lap stamp. It reads the priorities back itself,
        # so this plane's readback wait is inside the priorities span
        with _priorities_span():
            self.replay.drain_pending(pending[:2])


# ---------------------------------------------------------------------------
# Priority superstep (priority_plane="device"): N fused K-update dispatches
# chained in ONE lax.scan, with stratified sampling, IS weights, the batch
# gather, the train step, AND the priority write-back all running against
# the device-resident sum tree (replay/device_sum_tree.py). The host
# re-enters the loop only every N*K updates — for block ingestion, metrics,
# and snapshots — instead of fencing every dispatch with a host tree draw
# before it and a D2H priority drain after it.
# ---------------------------------------------------------------------------


def make_priority_superstep(
    cfg: R2D2Config,
    net: R2D2Network,
    num_dispatches: int,
    num_updates: int,
    donate: bool = True,
):
    """Build the single-chip superstep over a device-resident tree.

    Signature:
      superstep(state, stores, tree, num_seq_store, key) ->
        (state', tree', metrics-of-last-update)

    where `tree` is the DeviceSumTree's flat float32 array,
    `num_seq_store` the (num_blocks,) per-slot sequence counts (the
    zero-leaf clamp's input, uploaded per superstep — a few hundred
    bytes), and `key` a jax PRNG key consumed deterministically: one
    split per dispatch, K sub-keys per dispatch, one stratified (B,) draw
    per sub-key — the same draw structure as the host plane's K
    sequential SumTree.sample calls.

    Semantics (pinned by tests/test_superstep.py):
    - all K coordinate sets of a dispatch are drawn against the tree at
      dispatch entry (exactly like DeviceReplayBuffer.sample_and_run's
      K draws under one lock hold), and the K updates' priorities land
      after the K-scan in row order — last write wins on duplicate
      leaves, like the host drain;
    - consecutive dispatches inside the superstep see each other's
      write-backs immediately (there is no host to lag behind), so the
      one-dispatch priority lag of the deferred-drain protocol does not
      exist here — dispatch d+1 samples the post-d tree. A superstep of
      N on `key` is bit-identical to N sequential superstep-1 calls on
      the key sequence jax.random.split(key, N) (the equivalence test;
      superstep-1 consumes its key directly), NOT bit-identical to the
      host plane's deferred drain;
    - blocks ingested while the superstep is in flight are dispatched
      after it on the device stream (DeviceReplayBuffer.superstep_run
      installs the output tree under the buffer lock), so their leaf
      writes land on top of the superstep's — the same verdict the host
      pointer-window mask reaches for overwritten slots."""
    from r2d2_tpu.replay import device_sum_tree as dst

    multi_core = make_multi_update_core(cfg, net, num_updates)
    L = dst.tree_layers(cfg.num_sequences)
    S = cfg.seqs_per_block
    B = cfg.batch_size
    K = num_updates

    def superstep(state: TrainState, stores, tree, num_seq_store, key):
        def dispatch(carry, kd):
            state, tree = carry
            keys = jax.random.split(kd, K)
            # K stratified (B,) draws against the dispatch-entry tree
            leaf = jax.vmap(lambda k: dst.tree_sample(tree, L, B, k))(keys)
            # weights from the UNCLAMPED sampled leaves (host contract:
            # SumTree.sample computes weights before the zero-leaf clamp)
            w = jax.vmap(
                lambda li: dst.is_weights(tree, L, li, cfg.is_exponent)
            )(leaf)
            b = leaf // S
            s = jnp.minimum(leaf % S, jnp.maximum(num_seq_store[b] - 1, 0))
            state, metrics, prios = multi_core(state, stores, b, s, w)
            idxes = b * S + s  # clamped global slots, like the host drain

            def write_back(tree, row):
                li, td = row
                return dst.tree_update(tree, L, li, td, cfg.prio_exponent), None

            tree, _ = jax.lax.scan(write_back, tree, (idxes, prios))
            return (state, tree), metrics

        # N=1 consumes the key DIRECTLY so that superstep-N on `key` is
        # bit-identical to N sequential superstep-1 calls on
        # jax.random.split(key, N) — the equivalence tests' contract
        if num_dispatches > 1:
            keys = jax.random.split(key, num_dispatches)
        else:
            keys = key[None]
        (state, tree), metrics = jax.lax.scan(dispatch, (state, tree), keys)
        return state, tree, jax.tree.map(lambda x: x[-1], metrics)

    return jax.jit(superstep, donate_argnums=(0, 2) if donate else ())


def make_sharded_priority_superstep(
    cfg: R2D2Config,
    net: R2D2Network,
    mesh,
    num_dispatches: int,
    num_updates: int,
    donate: bool = True,
):
    """The dp-sharded superstep: shard_map over the mesh's dp axis with
    per-shard trees stacked (dp, tree_size) alongside the sharded stores.

    Each shard draws its (B/dp,) sub-batches from its OWN tree shard and
    writes its priorities back locally — zero cross-device tree traffic.
    IS weights use the host sharded plane's batch-global contract: raw
    sampled priorities feed make_multi_update_core(is_from_priorities=
    True), which normalizes each update's batch against the global
    minimum via a pmin over dp (the same formula ShardedDeviceReplay
    applies on host).

    Signature: superstep(state, stores, trees, num_seq_store, keys) ->
      (state', trees', metrics) with trees (dp, tree_size), num_seq_store
      (dp, nb/dp), keys (dp, 2) raw PRNG key data — one independent
      stream per shard, mirroring the host plane's per-shard
      Generators."""
    from jax.sharding import PartitionSpec as P

    from r2d2_tpu.parallel.jax_compat import shard_map
    from r2d2_tpu.parallel.mesh import dp_manual_axes
    from r2d2_tpu.replay import device_sum_tree as dst
    from r2d2_tpu.replay.control_plane import shard_config

    dp = int(mesh.shape["dp"])
    scfg = shard_config(cfg, dp)
    multi_core = make_multi_update_core(
        cfg, net, num_updates, axis_name="dp", is_from_priorities=True
    )
    L = dst.tree_layers(scfg.num_sequences)
    S = scfg.seqs_per_block
    B = scfg.batch_size  # B/dp
    K = num_updates

    def body(state: TrainState, stores, trees, num_seq_store, keys):
        # local views: trees (1, tree_size), num_seq_store (1, nb/dp),
        # keys (1, 2); stores = this shard's (nb/dp, ...) slabs
        tree, nss = trees[0], num_seq_store[0]

        def dispatch(carry, kd):
            state, tree = carry
            ks = jax.random.split(kd, K)
            leaf = jax.vmap(lambda k: dst.tree_sample(tree, L, B, k))(ks)
            # RAW priorities: the multi core pmin-normalizes per update
            p = jax.vmap(lambda li: dst.priorities_of(tree, L, li))(leaf)
            b = leaf // S
            s = jnp.minimum(leaf % S, jnp.maximum(nss[b] - 1, 0))
            state, metrics, prios = multi_core(state, stores, b, s, p)
            idxes = b * S + s

            def write_back(tree, row):
                li, td = row
                return dst.tree_update(tree, L, li, td, cfg.prio_exponent), None

            tree, _ = jax.lax.scan(write_back, tree, (idxes, prios))
            return (state, tree), metrics

        # same N=1 direct-consumption rule as the single-chip superstep
        if num_dispatches > 1:
            dkeys = jax.random.split(keys[0], num_dispatches)
        else:
            dkeys = keys[0][None]
        (state, tree), metrics = jax.lax.scan(dispatch, (state, tree), dkeys)
        return state, tree[None], jax.tree.map(lambda x: x[-1], metrics)

    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P("dp"), P("dp"), P("dp"), P("dp")),
        out_specs=(P(), P("dp"), P()),
        axis_names=dp_manual_axes(mesh),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0, 2) if donate else ())
