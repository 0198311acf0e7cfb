"""Training orchestrator (L5) and CLI (L6).

Reference topology (reference train.py:29-62): 8 actor processes + a replay
process (3 service threads) + the learner in the main process, wired by
pickling mp.Queues. On TPU the device does the heavy lifting in two jitted
functions (act, train_step), so the host side collapses to threads sharing
the replay object directly — no pickling, no process forks (and it must:
this class of host has few cores; SURVEY.md section 5.8 maps the reference's
3 queues onto (a) direct add_block calls, (b) an in-memory prefetch queue of
device-resident batches, (c) a direct update_priorities call).

Two modes:
- inline: strict actor/learner alternation in one thread — the minimum
  end-to-end slice of SURVEY.md section 7.2, used by integration tests.
- threaded: actor thread + sampler/prefetch thread + learner loop, with the
  reference's backpressure depth (batch queue 8: train.py:35).

Cadences preserved (SURVEY.md section 2.6): publish weights every 4
updates, actor pull every 400 env steps, target sync every 2000 (inside the
jitted step), checkpoint every 500, stop at training_steps, sampling gated
on learning_starts.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import queue
import signal
import sys
import threading
import time
from typing import Callable, Optional

import jax
import numpy as np

import jax.numpy as jnp

from r2d2_tpu.actor import HostEnvPool, ParamStore, VectorizedActor
from r2d2_tpu.config import PRESETS, R2D2Config, parse_overrides, tiny_test
from r2d2_tpu.envs import make_env
from r2d2_tpu.envs.catch import CatchVecEnv
from r2d2_tpu.learner import (
    DeviceBatch,
    init_train_state,
    make_fused_multi_train_step,
    make_manual_train_step,
    make_sharded_fused_multi_train_step,
    make_stacked_batch_train_step,
    make_train_step,
)
from r2d2_tpu.ops.epsilon import epsilon_ladder
from r2d2_tpu.parallel.mesh import (
    make_mesh,
    manual_batch_sharding,
    replicated_sharding,
    shard_batch,
)
from r2d2_tpu.replay.device_store import DeviceReplayBuffer
from r2d2_tpu.replay.replay_buffer import ReplayBuffer
from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay
from r2d2_tpu.replay.tiered_store import (
    StagedChunk,
    TieredPrefetchPipeline,
    TieredReplayBuffer,
    stage_chunk,
)
from r2d2_tpu.utils.checkpoint import latest_checkpoint_step, restore_checkpoint, save_checkpoint
from r2d2_tpu.utils.faults import fault_point, install_from_env, total_retries, with_retries
from r2d2_tpu.utils.metrics import MetricsLogger
from r2d2_tpu.utils import profiling
from r2d2_tpu.utils.profiling import TransferTimer, span, spanned, start_profiler_server, step_span
from r2d2_tpu.utils.supervision import PREEMPT_EXIT_CODE, Supervisor, WorkerStalledError


def _is_procmaze(name: str) -> bool:
    from r2d2_tpu.envs.procmaze import is_procmaze_name

    return is_procmaze_name(name)


def _build_procmaze(cfg: R2D2Config, name: str):
    from r2d2_tpu.envs.procmaze import build_procmaze_env

    return build_procmaze_env(cfg.obs_shape, cfg.max_episode_steps, name)


def _build_multitask_family(cfg: R2D2Config, name: str):
    """Functional core for the keydoor/drift/banditgrid families (None if
    the name is not one of them) — each family's single build_*_env
    factory, driven by cfg geometry like procmaze above."""
    from r2d2_tpu.envs.banditgrid import build_banditgrid_env, is_banditgrid_name
    from r2d2_tpu.envs.drift import build_drift_env, is_drift_name
    from r2d2_tpu.envs.keydoor import build_keydoor_env, is_keydoor_name

    if is_keydoor_name(name):
        return build_keydoor_env(cfg.obs_shape, cfg.max_episode_steps, name)
    if is_drift_name(name):
        return build_drift_env(cfg.obs_shape, cfg.max_episode_steps, name)
    if is_banditgrid_name(name):
        return build_banditgrid_env(cfg.obs_shape, cfg.max_episode_steps, name)
    return None


def build_vec_env(cfg: R2D2Config, seed: int = 0):
    """One vectorized env spanning cfg.num_actors slots."""
    from r2d2_tpu.envs.catch import catch_params, is_catch_name

    name = cfg.env_name.lower()
    if is_catch_name(name):
        return CatchVecEnv(
            num_envs=cfg.num_actors, height=cfg.obs_shape[0], width=cfg.obs_shape[1],
            seed=seed, **catch_params(name),
        )
    if _is_procmaze(name):
        from r2d2_tpu.envs.functional import FnVecEnv

        return FnVecEnv(
            _build_procmaze(cfg, name), num_envs=cfg.num_actors, seed=seed
        )
    family_env = _build_multitask_family(cfg, name)
    if family_env is not None:
        from r2d2_tpu.envs.functional import FnVecEnv

        return FnVecEnv(family_env, num_envs=cfg.num_actors, seed=seed)
    envs = [make_env(cfg, seed=seed + i) for i in range(cfg.num_actors)]
    if cfg.env_pool_workers > 0:
        from r2d2_tpu.actor import ThreadedHostEnvPool

        return ThreadedHostEnvPool(envs, workers=cfg.env_pool_workers)
    return HostEnvPool(envs)


def build_fn_env(cfg: R2D2Config):
    """Functional (jit/vmap-safe) env core for the on-device collector."""
    from r2d2_tpu.envs.catch import CatchEnv, catch_params, is_catch_name

    name = cfg.env_name.lower()
    if is_catch_name(name):
        return CatchEnv(
            height=cfg.obs_shape[0], width=cfg.obs_shape[1], **catch_params(name)
        )
    if _is_procmaze(name):
        return _build_procmaze(cfg, name)
    family_env = _build_multitask_family(cfg, name)
    if family_env is not None:
        return family_env
    if name == "scripted" or name.startswith("scripted:"):
        from r2d2_tpu.envs.fake import ScriptedFnEnv

        # "scripted:A" pins the action space (same rule as make_env)
        adim = int(name.split(":", 1)[1]) if ":" in name else cfg.action_dim
        return ScriptedFnEnv(obs_shape=cfg.obs_shape, action_dim=adim)
    raise ValueError(
        f"env {cfg.env_name!r} has no pure-JAX functional core; "
        "use collector='host' for emulator/host-protocol envs"
    )


class _HostPlane:
    """Host numpy replay; batches ship host->device each update. With a
    mesh, batches shard over dp and XLA inserts the gradient psum. Batches
    are copied out of the store at sample time, so queued items can never
    go stale (pipelined == inline here).

    partitioning="manual" (the tp×fsdp path GSPMD can't compile — see
    learner.make_manual_train_step): the step is an explicit shard_map over
    every mesh axis and the batch additionally splits over fsdp (ZeRO-2),
    so this plane lifts batches with manual_batch_sharding instead of the
    dp-only shard_batch."""

    steps_per_update = 1

    def __init__(self, tr: "Trainer"):
        self.tr = tr
        self.replay = ReplayBuffer(tr.cfg)
        self.manual = (
            tr.mesh is not None and tr.cfg.resolved_partitioning == "manual"
        )
        if self.manual:
            self.step_fn = make_manual_train_step(tr.cfg, tr.mesh)
        else:
            self.step_fn = make_train_step(tr.cfg, tr.net)

    def sample(self, pipelined: bool = False):
        with span("r2d2.replay.sample"):
            b = self.replay.sample_batch(self.tr.sample_rng)

            def lift():
                fault_point("host_plane.h2d")
                dev = DeviceBatch.from_sampled(b)
                if self.manual:
                    sh = manual_batch_sharding(self.tr.mesh)
                    dev = jax.tree.map(lambda x: jax.device_put(x, sh), dev)
                elif self.tr.mesh is not None:
                    dev = DeviceBatch(*shard_batch(self.tr.mesh, tuple(dev)))
                return dev

            # a flaky h2d re-lifts the already-drawn host batch: retries
            # never touch the sampling RNG, so the draw stream is stable
            dev = with_retries(lift, "host_plane.h2d")
            return "batch", dev, b.idxes, (b.old_ptr, b.old_advances)

    def update(self, state, item):
        _, dev, idxes, (old_ptr, old_adv) = item
        state, m, priorities = self.step_fn(state, dev)
        self.replay.update_priorities(idxes, np.asarray(priorities), old_ptr, old_adv)
        return state, m


class _TieredPlane:
    """Full-capacity host store + double-buffered HBM staging
    (replay/tiered_store.py): the plane that serves the paper's 2M-
    transition capacity at device-plane update throughput.

    A staging thread draws K batches under one lock hold, host-gathers
    their windows through the vectorized native multi-gather, and lifts
    the stacked chunk into HBM while the learner's K-update scan
    (make_stacked_batch_train_step) consumes the previous chunk — the
    host->device copy runs behind compute instead of ahead of it. The
    priority readback is deferred one dispatch exactly like _HbmPlane's;
    staleness needs no extra machinery because chunks are BY-VALUE (bytes
    copied out at stage time) and carry their stage-time window stamps.
    The TransferTimer's overlap fraction lands in the metrics stream via
    log_extras."""

    def __init__(self, tr: "Trainer"):
        self.tr = tr
        self.replay = TieredReplayBuffer(tr.cfg)
        self.K = self.steps_per_update = tr.cfg.updates_per_dispatch
        self._pending = None  # deferred (priorities, chunk) readback
        self.xfer = TransferTimer()
        self.multi_fn = make_stacked_batch_train_step(tr.cfg, tr.net, self.K)
        # r2d2: ephemeral(lazily rebuilt by _ensure_pipeline on the next sample; capture_pending stops it with an RNG rewind so the resumed pipeline re-draws identically)
        self._pipe: Optional[TieredPrefetchPipeline] = None

    def _ensure_pipeline(self) -> TieredPrefetchPipeline:
        # lazy: started on first sample, i.e. after warmup opened the
        # sampling gate (and restartable after a finish_updates drain)
        if self._pipe is None:
            self._pipe = TieredPrefetchPipeline(
                self.replay, self.tr.sample_rng, self.K, timer=self.xfer
            )
        return self._pipe

    def sample(self, pipelined: bool = False):
        if self.tr.cfg.deterministic_staging:
            # synchronous stage on the consumer thread: no staging-thread
            # RNG race with write-backs, so the sampling stream is
            # bit-reproducible (the chaos suite's resume contract); trades
            # away the pipeline's transfer/compute overlap
            with span("r2d2.replay.stage"):
                chunk = stage_chunk(
                    self.replay, self.tr.sample_rng, self.K, self.xfer
                )
                return "staged", chunk, None, None
        # both modes consume the staging pipeline: it IS the prefetcher
        # (threaded mode's sampler thread just forwards chunks into its
        # queue, adding one more buffered chunk of depth)
        with span("r2d2.replay.stage"):
            return "staged", self._ensure_pipeline().get(), None, None

    def update(self, state, item):
        _, chunk, _, _ = item
        state, m, priorities = self.multi_fn(state, chunk.batch)
        priorities.copy_to_host_async()
        # deferred one dispatch (_HbmPlane._multi_update rationale): the
        # readback lands while the NEXT chunk executes
        prev, self._pending = self._pending, (priorities, chunk)
        if prev is not None:
            self.drain_pending(prev)
        return state, m

    def drain_pending(self, pending=None) -> None:
        """Apply a deferred (priorities, chunk) pair. Called with the
        previous pair each update; called with no argument on run-mode
        exit, where it ALSO stops the staging thread — an undrained staged
        chunk is simply dropped (by-value bytes, no tree writes pending),
        leaving the sum tree consistent."""
        if pending is None:
            if self._pipe is not None:
                self._pipe.stop()
                self._pipe = None
            pending, self._pending = self._pending, None
        if pending is None:
            return
        prios, chunk = pending
        for row, idx in zip(np.asarray(prios), chunk.idxes):
            self.replay.update_priorities(idx, row, chunk.old_ptr, chunk.old_advances)

    def capture_pending(self) -> Optional[dict]:
        """Preemption capture: serialize the deferred write-back INSTEAD of
        applying it. In an uninterrupted run the next draw happens before
        this write-back lands (update() applies it one dispatch later), so
        draining it at preemption would make the resumed draw see a tree
        the uninterrupted run never had — restore_pending re-queues it so
        the resumed iteration replays the exact apply order. Also stops the
        staging pipeline with an RNG rewind: queued/in-flight chunks are
        discarded and their draws re-happen identically after resume."""
        if self._pipe is not None:
            self._pipe.stop(rewind=True)
            self._pipe = None
        pending, self._pending = self._pending, None
        if pending is None:
            return None
        prios, chunk = pending
        return {
            "prios": np.asarray(prios),
            "idxes": np.asarray(chunk.idxes),
            "old_ptr": np.asarray(chunk.old_ptr, np.int64),
            "old_advances": np.asarray(chunk.old_advances, np.int64),
        }

    def restore_pending(self, d: dict) -> None:
        chunk = StagedChunk(
            batch=None,  # already consumed pre-preempt; only stamps remain
            idxes=np.asarray(d["idxes"]),
            old_ptr=int(np.asarray(d["old_ptr"])[()]),
            old_advances=int(np.asarray(d["old_advances"])[()]),
            env_steps=0,
        )
        self._pending = (np.asarray(d["prios"]), chunk)

    def log_extras(self) -> dict:
        # disk_stats() is {} when the disk tier is off, so the default
        # metrics stream is unchanged
        return {**self.xfer.stats(), **self.replay.disk_stats()}


class _HbmPlane:
    """HBM replay on one chip (replay_plane="device": replay/device_store.py,
    a plain-jit step) or dp-sharded over the mesh ("sharded":
    replay/sharded_store.py, a shard_map step with local gathers per shard
    and a gradient psum over dp). The two differ in the store they build and
    the step wrapper they call; everything else is one path.

    An update is ONE thing for every K = updates_per_dispatch >= 1: K
    coordinate sets drawn when the update dispatches, under the store's lock
    (an item waiting in threaded mode's queue therefore holds no coordinates
    that a concurrent block write could retarget), one dispatch that gathers
    and updates K times in-jit, and the (K, ...) priorities read back one
    dispatch late. priority_plane="device" swaps that for the in-jit N x K
    superstep, which draws and writes back against the HBM tree itself."""

    def __init__(self, tr: "Trainer"):
        cfg = tr.cfg
        self.tr = tr
        self.K = self.steps_per_update = cfg.updates_per_dispatch
        self._pending = None  # deferred (priorities, draws) readback
        self.device_priority = cfg.priority_plane == "device"
        sharded = cfg.replay_plane == "sharded"
        if sharded and tr.mesh is None:
            raise ValueError("replay_plane='sharded' needs dp_size*tp_size > 1")
        self.replay = ShardedDeviceReplay(cfg, tr.mesh) if sharded else DeviceReplayBuffer(cfg)
        on_mesh = (tr.mesh,) if sharded else ()
        if self.device_priority:
            from r2d2_tpu.megastep import (
                make_priority_superstep,
                make_sharded_priority_superstep,
            )

            build = make_sharded_priority_superstep if sharded else make_priority_superstep
            self.N = cfg.superstep_dispatches
            self.steps_per_update = self.N * self.K
            self.superstep_fn = build(cfg, tr.net, *on_mesh, self.N, self.K)
            # key stream derived from the STEP COUNTER, not carried state:
            # a --resume at step s re-derives superstep s/(N*K)'s key
            # exactly, with nothing extra to snapshot
            self._superstep_base_key = jax.random.PRNGKey(cfg.seed + 4)
        else:
            build = make_sharded_fused_multi_train_step if sharded else make_fused_multi_train_step
            self.multi_fn = build(cfg, tr.net, *on_mesh, self.K)

    def sample(self, pipelined: bool = False):
        # nothing is drawn here: the update draws its own coordinates when it
        # dispatches (the superstep in-jit, against the live tree)
        return ("superstep" if self.device_priority else "multi", None, None, None)

    def update(self, state, item):
        if item[0] == "superstep":
            return self._superstep_update(state)
        return self._multi_update(state)

    def _superstep_update(self, state):
        """priority_plane="device": ONE dispatch runs N x K updates with
        sampling, IS weights, gather, train, and priority write-back all
        in-jit against the HBM tree (megastep.make_priority_superstep and
        its sharded twin). Nothing is drawn on host, nothing drains
        afterwards — the host's only work here is deriving the dispatch key
        (the store makes of it one key, or one stream per dp shard) and
        swapping the tree handle under the buffer lock."""
        key = self.replay.superstep_keys(jax.random.fold_in(
            self._superstep_base_key, self.tr._step // self.steps_per_update
        ))

        def dispatch(stores, tree, nss):
            new_state, tree_out, m = self.superstep_fn(state, stores, tree, nss, key)
            return tree_out, (new_state, m)

        return self.replay.superstep_run(dispatch)

    def _multi_update(self, state):
        """K updates in one dispatch: draw + dispatch under one lock hold
        (the store's sample_and_run), then apply the (K, B) or (K, dp, B/dp)
        priorities row-by-row under each draw's own staleness window (per
        shard on the sharded store).

        The priority readback is DEFERRED one dispatch: reading this
        chunk's priorities immediately would stall the host for the chunk's
        execution plus a full device->host round trip; instead the transfer
        is started async and collected while the NEXT chunk executes. Tree
        priorities lag one extra chunk (bounded, same class as the
        reference's ~12-batch pipeline lag); the pointer-window mask still
        rejects rows whose slots were overwritten meanwhile."""

        def dispatch(stores, draws):
            b = jnp.asarray(np.stack([d.b for d in draws]))
            s = jnp.asarray(np.stack([d.s for d in draws]))
            w = jnp.asarray(np.stack([d.is_weights for d in draws]))
            return self.multi_fn(state, stores, b, s, w)

        draws, (new_state, m, priorities) = self.replay.sample_and_run(
            self.tr.sample_rng, self.K, dispatch
        )
        priorities.copy_to_host_async()
        prev, self._pending = self._pending, (priorities, draws)
        if prev is not None:
            self.drain_pending(prev)
        return new_state, m

    def drain_pending(self, pending=None) -> None:
        """Apply a deferred (priorities, draws) pair to the tree. Called
        with the previous chunk's pair each update, and once with the final
        in-flight pair when a run mode exits."""
        if pending is None:
            pending, self._pending = self._pending, None
        if pending is None:
            return
        prios, draws = pending
        for row, d in zip(np.asarray(prios), draws):
            # old_advances: a free-running collector could lap the whole
            # ring while this chunk's readback was deferred — the stamp
            # drops the batch instead of mis-applying it (control_plane)
            self.replay.update_priorities(d.idxes, row, d.old_ptr, d.old_advances)

    def capture_pending(self) -> Optional[dict]:
        """Preemption capture of the deferred readback — same apply-
        order-preservation rationale as _TieredPlane.capture_pending. The
        stamps are (K,) on the device store and (K, dp) on the sharded one,
        whose restored draws carry them as (dp,) arrays."""
        pending, self._pending = self._pending, None
        if pending is None:
            return None
        prios, draws = pending
        return {
            "prios": np.asarray(prios),
            "idxes": np.stack([np.asarray(d.idxes) for d in draws]),
            "old_ptr": np.asarray([d.old_ptr for d in draws], np.int64),
            "old_advances": np.asarray([d.old_advances for d in draws], np.int64),
        }

    def restore_pending(self, d: dict) -> None:
        import types

        draws = [
            types.SimpleNamespace(idxes=np.asarray(idx), old_ptr=p, old_advances=a)
            for idx, p, a in zip(d["idxes"], d["old_ptr"], d["old_advances"])
        ]
        self._pending = (np.asarray(d["prios"]), draws)


class _MultiHostPlane:
    """Per-process local replay shards over a GLOBAL (possibly multi-
    process) mesh; collective shard_map updates with in-step IS
    normalization (replay/multihost_store.py). Every process runs the
    same Trainer loop — updates are SPMD-collective, so processes stay in
    lockstep through the step dispatches themselves; collection, logging,
    and the priority drain are host-local.

    Every update is ONE shard_map K-scan dispatch of K =
    updates_per_dispatch >= 1 collective updates, with the priority readback
    deferred one dispatch (replay.run_step_k): the device and sharded
    planes' one path (_HbmPlane), on the scale-out plane."""

    def __init__(self, tr: "Trainer"):
        from r2d2_tpu.replay.multihost_store import MultiHostShardedReplay

        if tr.mesh is None:
            raise ValueError("multihost plane needs a mesh")
        self.tr = tr
        self.replay = MultiHostShardedReplay(tr.cfg, tr.mesh, seed=tr.cfg.seed + 3)
        self.K = self.steps_per_update = tr.cfg.updates_per_dispatch
        self.multi_fn = make_sharded_fused_multi_train_step(
            tr.cfg, tr.net, tr.mesh, self.K, is_from_priorities=True
        )

    def sample(self, pipelined: bool = False):
        # draws happen inside run_step_k, atomically with the dispatch
        return ("multihost", None, None, None)

    def update(self, state, item):
        return self.replay.run_step_k(self.multi_fn, state, self.K)

    def drain_pending(self, pending=None) -> None:
        self.replay.drain_pending(pending)


_PLANES = {
    "host": _HostPlane,
    "tiered": _TieredPlane,
    "device": _HbmPlane,
    "sharded": _HbmPlane,
    "multihost": _MultiHostPlane,
}


class Trainer:
    # set-up is read through the spans' aggregates (profiling.counters): it
    # runs before any traced window
    @spanned("r2d2.setup.init")
    def __init__(
        self,
        cfg: R2D2Config,
        vec_env=None,
        fn_env=None,
        resume: bool = False,
        metrics: Optional[MetricsLogger] = None,
        profile_dir: Optional[str] = None,
        profile_steps: int = 20,
    ):
        from r2d2_tpu.utils.compilation_cache import enable_compilation_cache

        enable_compilation_cache()
        # profiling hooks (SURVEY.md 5.1): trace the first `profile_steps`
        # post-warmup updates — the steady-state pipeline shape
        self.profile_dir = profile_dir
        self._profile_remaining = profile_steps if profile_dir else 0
        self._profile_active = False
        self._span_mark: dict = {}  # profiling.counters() at the last metrics row
        self.cfg = cfg
        self.fn_env = None
        if cfg.collector == "device":
            self.vec_env = None
            self.fn_env = fn_env if fn_env is not None else build_fn_env(cfg)
            env_action_dim = self.fn_env.NUM_ACTIONS
        else:
            self.vec_env = vec_env if vec_env is not None else build_vec_env(cfg, seed=cfg.seed)
            env_action_dim = self.vec_env.action_dim
        if env_action_dim != cfg.action_dim:
            cfg = cfg.replace(action_dim=env_action_dim)
            self.cfg = cfg

        # mesh: dp x tp when the config asks for parallelism (collectives
        # ride ICI on a real slice; tests run on the 8-fake-device CPU mesh)
        self.mesh = None
        if cfg.replay_plane == "multihost":
            # GLOBAL mesh over every process's devices (parallel/multihost);
            # dp_size<=1 means "all global devices". A partial dp_size is
            # rejected here: slicing the global device list could leave a
            # process with zero local shards.
            from r2d2_tpu.parallel.multihost import make_global_mesh

            n_global = len(jax.devices())
            if cfg.dp_size > 1 and cfg.dp_size != n_global:
                raise ValueError(
                    f"multihost plane spans ALL global devices: dp_size="
                    f"{cfg.dp_size} != {n_global} devices (set dp_size<=1 "
                    "to mean 'all', or use replay_plane='sharded' for a "
                    "single-host subset)"
                )
            self.mesh = make_global_mesh(
                dp=cfg.dp_size if cfg.dp_size > 1 else None, tp=1
            )
        elif cfg.dp_size * cfg.tp_size * cfg.fsdp_size > 1:
            # fsdp > 1 grows the third mesh axis that shards the Adam
            # mu/nu trees (parallel/sharding_map.py); the replay layout
            # stays dp-determined, so --resume/--reshard snapshots are
            # fsdp-agnostic (their topology manifests record dp/tp only).
            n_mesh = cfg.dp_size * cfg.tp_size * cfg.fsdp_size
            self.mesh = make_mesh(dp=cfg.dp_size, tp=cfg.tp_size,
                                  devices=jax.devices()[:n_mesh],
                                  fsdp=cfg.fsdp_size)

        self.net, self.state = init_train_state(cfg, jax.random.PRNGKey(cfg.seed))
        if self.mesh is not None:
            if cfg.replay_plane != "multihost":
                # LSTM/encoder kernels shard over tp; tp=1 degenerates to
                # replicated. Plain-jit planes: GSPMD partitions from
                # these shardings alone. The "sharded" shard_map plane is
                # manual over dp only (axis_names={"dp"}), so the same tp
                # shardings partition the per-dp-shard body.
                from r2d2_tpu.parallel.mesh import train_state_shardings

                self.state = jax.device_put(
                    self.state, train_state_shardings(self.state, self.mesh)
                )
            else:
                # multihost declares P() (dp-replicated) params and tp=1
                self.state = jax.device_put(self.state, replicated_sharding(self.mesh))
        self.env_steps_offset = 0
        self.wall_minutes_offset = 0.0
        self._resumed = False
        if resume and latest_checkpoint_step(cfg.checkpoint_dir) is not None:
            self.state, self.env_steps_offset, self.wall_minutes_offset = restore_checkpoint(
                cfg.checkpoint_dir, self.state
            )
            if self.mesh is None:
                # orbax hands back arrays COMMITTED to their device; a
                # fresh single-device state is uncommitted, and a committed
                # argument lowers to a different program (and commits every
                # output downstream) — left as is, a --resume process
                # recompiles the step programs the first process cached.
                # One host round trip of the state, once per resume.
                self.state = jax.tree.map(
                    lambda x: jnp.asarray(np.asarray(x)), self.state
                )
            self._resumed = True

        # first update after THIS construction compiles the jitted step;
        # the profiler gate skips it even when resuming from step > 0
        self._initial_step = int(self.state.step)
        # host-side mirror of state.step: reading the device scalar every
        # update would force a full stream sync per update; increments
        # are known exactly (updates_per_dispatch per plane.update)
        self._step = self._initial_step
        _quantum = cfg.updates_per_dispatch * cfg.superstep_dispatches
        if self._initial_step % _quantum != 0:
            raise ValueError(
                f"resumed step {self._initial_step} is not a multiple of "
                f"updates_per_dispatch*superstep_dispatches={_quantum}; "
                "training would overshoot training_steps — resume with the "
                "N and K the checkpoint was trained with (or N=K=1)"
            )
        self.sample_rng = np.random.default_rng(cfg.seed + 2)
        # deferred metrics queue (_log / _flush_log): latest un-emitted
        # (m, step, extra); epoch-zero stamp emits the FIRST record eagerly
        self._pending_metrics = None
        self._last_log_emit = 0.0
        # set by the CLI to its start-up banner; stamped into the FIRST
        # metrics record, then cleared
        self.runtime_stamp: Optional[dict] = None
        # preemption protocol: request_preempt (usually via SIGTERM inside
        # a run mode's _sigterm_to_preempt window) sets the event; the run
        # loop honors it at the next iteration boundary, snapshots replay +
        # mid-run carry, writes a finalized checkpoint, and the CLI exits
        # with PREEMPT_EXIT_CODE
        self.preempted = False
        self._preempt = threading.Event()
        self._snap_thread: Optional[threading.Thread] = None
        self._resume_carry: dict = {}
        self.plane = _PLANES[cfg.replay_plane](self)
        self.replay = self.plane.replay
        if self._resumed and cfg.snapshot_replay:
            # restored env steps are part of the run total already counted
            # by env_steps_offset from the learner checkpoint; rebase so
            # the sum isn't double-counted. The offset is a GLOBAL total,
            # so a multi-process run subtracts the GLOBAL restored count
            # (each host's snapshot holds only its local shards' steps).
            # EVERY process participates in the collective unconditionally
            # — a host whose snapshot is missing contributes 0, and a
            # failed restore is agreed across hosts — because a collective
            # guarded by per-host file checks deadlocks the others.
            restored, failed = 0, 0
            try:
                if self._restore_replay_snapshot():
                    restored = self.replay.env_steps
            except Exception as e:  # noqa: BLE001 — agreed below
                failed = 1
                restore_err = e
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                gathered = multihost_utils.process_allgather(
                    np.asarray([restored, failed], np.int64)
                )
                restored = int(gathered[:, 0].sum())
                if int(gathered[:, 1].sum()):
                    bad = [int(p) for p in np.nonzero(gathered[:, 1])[0]]
                    raise RuntimeError(
                        f"replay snapshot restore failed on process(es) "
                        f"{bad}"
                    ) from (restore_err if failed else None)
            elif failed:
                raise restore_err
            self.env_steps_offset -= restored
        self.param_store = ParamStore(self.state.params)
        if cfg.collector == "device":
            from r2d2_tpu.collect import DeviceCollector

            self.actor = DeviceCollector(
                cfg, self.net, self.param_store, self.fn_env, self.replay,
                seed=cfg.seed + 1,
            )
        else:
            self.actor = VectorizedActor(
                cfg,
                self.net,
                self.param_store,
                self.vec_env,
                epsilon_ladder(cfg.num_actors, cfg.base_eps, cfg.eps_alpha),
                self.replay.add_block,
                seed=cfg.seed + 1,
            )
        self.metrics = metrics or MetricsLogger(cfg.metrics_path, cfg.log_interval)
        if self._resumed:
            self._maybe_restore_carry()

    # ---------------------------------------------------- preemption / carry

    def request_preempt(self, signum=None, frame=None) -> None:
        """Ask the run loop to cut at its next iteration boundary.
        Signal-handler-safe: sets a flag and returns — a SIGTERM landing
        mid-update lets the update finish, so the cut is always at a clean
        step boundary."""
        self._preempt.set()

    def _preempt_now(self) -> bool:
        """Checked once per run-loop iteration. Multi-process runs agree
        via an UNCONDITIONAL allgather — the loop is in lockstep through
        the collective update dispatches, so every process reaches this
        the same number of times, and any host's SIGTERM cuts ALL hosts at
        the same step (a guarded collective would deadlock the others)."""
        local = 1 if self._preempt.is_set() else 0
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            local = int(multihost_utils.process_allgather(np.int32(local)).sum())
        if local:
            self.preempted = True
        return bool(local)

    @contextlib.contextmanager
    def _sigterm_to_preempt(self):
        """Route SIGTERM into the preemption protocol for the enclosed run.
        Installed only on the main thread (signal.signal raises ValueError
        elsewhere — library callers driving a Trainer from a worker thread
        keep their process-level handler and can call request_preempt
        themselves); the previous handler is restored on exit."""
        try:
            prev = signal.signal(
                signal.SIGTERM, lambda s, f: self.request_preempt(s, f)
            )
        except ValueError:
            prev = None
        try:
            yield
        finally:
            if prev is not None:
                signal.signal(signal.SIGTERM, prev)

    def _carry_payload(self) -> dict:
        """Everything OUTSIDE the replay tree and learner state that the
        next iteration reads: the cut step, the sampling RNG, the published
        params, any captured deferred priority write-back, and the actor /
        env episode streams. Together with the replay snapshot it rides in
        and the finalized checkpoint, a --resume restores the exact
        mid-run program point (bit-identical next update AND next draw,
        pinned by tests/test_chaos.py)."""
        carry = {
            "carry_step": np.asarray(self._step, np.int64),
            "sample_rng": np.asarray(
                json.dumps(self.sample_rng.bit_generator.state)
            ),
        }
        params, version = self.param_store.latest()
        carry["pub_version"] = np.asarray(version, np.int64)
        for j, leaf in enumerate(jax.tree.leaves(params)):
            carry[f"pub_{j}"] = np.asarray(leaf)
        capture = getattr(self.plane, "capture_pending", None)
        if capture is not None:
            pend = capture()
            if pend:
                for k, v in pend.items():
                    carry[f"pend_{k}"] = v
        env_state = None
        if self.vec_env is not None and hasattr(self.vec_env, "get_state"):
            env_state = self.vec_env.get_state()
        if self.cfg.collector == "device":
            for k, v in self.actor.carry_state().items():
                carry[f"actor_{k}"] = v
        elif env_state is not None:
            # host actor carry is only useful if the ENV also resumes
            # exactly; emulator pools without get_state fall back to fresh
            # episodes on resume (the actor's resync-style cold start)
            for k, v in self.actor.carry_state().items():
                carry[f"actor_{k}"] = v
            for k, v in env_state.items():
                carry[f"env_{k}"] = v
        return carry

    def _capture_carry_safe(self) -> Optional[dict]:
        """Preempt-path carry capture for the run modes' finally blocks: a
        capture failure must degrade to a carry-less snapshot (still a
        valid end-of-run-style resume), never mask the original unwind.
        Must run BEFORE finish_updates — capture_pending serializes the
        deferred write-back that finish_updates would otherwise apply."""
        if not (self.preempted and self.cfg.snapshot_replay):
            return None
        try:
            return self._carry_payload()
        except Exception:  # noqa: BLE001 — degrade, don't mask
            import traceback

            traceback.print_exc()
            return None

    def _maybe_restore_carry(self) -> None:
        """Rehydrate the mid-run carry a preemption snapshot stored. The
        carry is only valid at the exact step it was cut at: a snapshot
        lagging the checkpoint (e.g. a periodic snapshot plus a later
        crash) is still restored as DATA by the replay restore above, but
        its carry is discarded and the run falls back to fresh episode
        streams — data-safe either way."""
        carry = self._resume_carry
        if "carry_step" not in carry:
            return
        carry_step = int(np.asarray(carry["carry_step"])[()])
        if carry_step != self._initial_step:
            print(
                f"[resume] discarding mid-run carry cut at step {carry_step} "
                f"(checkpoint is at step {self._initial_step}); resuming "
                "with fresh episode streams",
                file=sys.stderr,
            )
            return
        self.sample_rng.bit_generator.state = json.loads(
            str(np.asarray(carry["sample_rng"])[()])
        )
        if "pub_version" in carry:
            treedef = jax.tree.structure(self.param_store._params)
            leaves = [
                jnp.asarray(carry[f"pub_{j}"])
                for j in range(treedef.num_leaves)
            ]
            with self.param_store._lock:
                self.param_store._params = jax.tree.unflatten(treedef, leaves)
                self.param_store.version = int(
                    np.asarray(carry["pub_version"])[()]
                )
        pend = {
            k[len("pend_"):]: v for k, v in carry.items()
            if k.startswith("pend_")
        }
        restore_pending = getattr(self.plane, "restore_pending", None)
        if pend and restore_pending is not None:
            restore_pending(pend)
        act = {
            k[len("actor_"):]: v for k, v in carry.items()
            if k.startswith("actor_")
        }
        if act and hasattr(self.actor, "restore_carry"):
            self.actor.restore_carry(act)
        envd = {
            k[len("env_"):]: v for k, v in carry.items()
            if k.startswith("env_")
        }
        if envd and self.vec_env is not None and hasattr(self.vec_env, "set_state"):
            self.vec_env.set_state(envd)

    def _finalize_preempt(self) -> None:
        """The preemption COMMIT: a finalized checkpoint at the cut step,
        written strictly AFTER the replay snapshot + carry landed. Resume
        keys off the latest finalized checkpoint, so a crash between the
        two leaves the previous checkpoint/snapshot pair in force — at no
        point does a checkpoint reference a snapshot that isn't on disk."""
        if latest_checkpoint_step(self.cfg.checkpoint_dir) == self._step:
            return  # the cadence crossing already checkpointed this step
        save_checkpoint(
            self.cfg.checkpoint_dir,
            self.state,
            self._global_env_steps(),
            self.wall_minutes_offset + (time.time() - self._start_time) / 60.0,
        )

    # ------------------------------------------------------------- plumbing

    def _profile_gate(self) -> None:
        """Start the trace AFTER the first update: update 1 compiles the
        jitted step, and a trace dominated by XLA compile time defeats the
        point (steady-state pipeline shape)."""
        if (
            self._profile_remaining > 0
            and not self._profile_active
            and self._step >= self._initial_step + 1
        ):
            profiling.start_trace(self.profile_dir)  # Python tracer off
            self._profile_active = True

    def _profile_tick(self, n: int) -> None:
        if self._profile_active:
            self._profile_remaining -= n
            if self._profile_remaining <= 0:
                self._stop_profile()

    def _one_update(self, item):
        fault_point("trainer.update")
        self._profile_gate()
        prev = self._step
        with step_span("r2d2.step.update", prev):
            self.state, m = self.plane.update(self.state, item)
        self._step += self.plane.steps_per_update
        step = self._step
        self._profile_tick(self.plane.steps_per_update)
        self._cadences(prev, step)
        return m, step

    def _cadences(self, prev: int, step: int) -> None:
        """Publish/checkpoint interval CROSSINGS, not equality: a K-update
        dispatch may jump past the exact multiple."""
        if step // self.cfg.publish_interval > prev // self.cfg.publish_interval:
            self.param_store.publish(self.state.params)
        if step // self.cfg.save_interval > prev // self.cfg.save_interval:
            # in a multi-process run every process calls this: orbax saves
            # distributed arrays collectively (needs a shared checkpoint
            # path across hosts, the standard orbax contract)
            save_checkpoint(
                self.cfg.checkpoint_dir,
                self.state,
                self._global_env_steps(),
                self.wall_minutes_offset + (time.time() - self._start_time) / 60.0,
            )
        if (
            self.cfg.snapshot_every > 0
            and step // self.cfg.snapshot_every > prev // self.cfg.snapshot_every
        ):
            # cut point: the metrics record preceding a snapshot must land
            # in the jsonl before the snapshot it describes
            self._flush_log()
            self._snapshot_async()

    def _global_env_steps(self) -> int:
        """Run-total env steps. replay.env_steps is host-local on the
        multihost plane, so a multi-process run sums it across processes
        (an allgather collective — safe here because every process reaches
        the checkpoint crossing in lockstep). env_steps_offset is ALREADY a
        global total restored from the checkpoint, so it is added exactly
        once, outside the sum."""
        local = self.replay.env_steps
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            local = int(multihost_utils.process_allgather(np.int64(local)).sum())
        return local + self.env_steps_offset

    def finish_updates(self) -> None:
        """Flush any deferred per-plane work (e.g. the HBM planes' in-flight
        priority readback). Every update-driving loop — the run modes here
        and external drivers (the live loop, tests) — calls this once when
        it stops updating."""
        drain = getattr(self.plane, "drain_pending", None)
        if drain is not None:
            drain()
        self._flush_log()

    def _replay_snapshot_path(self) -> str:
        # the multihost plane snapshots PER PROCESS (each host owns its
        # shards); a shared checkpoint dir must not collide across hosts
        if self.cfg.replay_plane == "multihost":
            return os.path.join(
                self.cfg.checkpoint_dir,
                f"replay_snapshot_p{jax.process_index()}.npz",
            )
        return os.path.join(self.cfg.checkpoint_dir, "replay_snapshot.npz")

    def _restore_replay_snapshot(self) -> bool:
        """Resume-time replay restore, topology-aware. Tries the exact
        same-layout restore of this process's own snapshot first; a
        TopologyMismatch (or a missing per-process file while OTHER
        snapshot files exist — a changed process layout renames them)
        falls through to the reshard path when cfg.reshard_on_resume is
        set, which regathers EVERY snapshot file the old run left and
        re-splits the slabs across the current layout
        (replay/reshard.py). Returns True if replay state was restored."""
        from r2d2_tpu.replay.reshard import reshard_replay, snapshot_paths
        from r2d2_tpu.replay.snapshot import TopologyMismatch, restore_replay

        snap = self._replay_snapshot_path()
        if os.path.exists(snap):
            try:
                self._resume_carry = restore_replay(self.replay, snap)
                return True
            except TopologyMismatch:
                if not self.cfg.reshard_on_resume:
                    raise
        else:
            others = snapshot_paths(self.cfg.checkpoint_dir)
            if not others:
                return False  # no snapshot at all: refill from scratch
            if not self.cfg.reshard_on_resume:
                from r2d2_tpu.replay.snapshot import (
                    _plain, read_manifest, snapshot_topology,
                )

                raise TopologyMismatch(
                    read_manifest(others[0]) or {},
                    _plain(snapshot_topology(self.replay, tp=self.cfg.tp_size)),
                    f"no snapshot named {os.path.basename(snap)} for this "
                    f"process, but {len(others)} snapshot file(s) exist — "
                    "a changed process layout",
                )
        self._resume_carry = reshard_replay(
            self.replay, snapshot_paths(self.cfg.checkpoint_dir)
        )
        return True

    def save_replay_snapshot(self, extra: Optional[dict] = None) -> str:
        """Persist full replay contents (replay/snapshot.py); returns the
        path. Run modes call this on exit when cfg.snapshot_replay is set.
        `extra` rides in the same atomic write (preemption carry: RNG,
        published params, deferred write-backs, actor/env streams). The
        embedded topology manifest carries the mesh's tp (the replay
        object alone cannot know it), keeping the snapshot portable
        across layouts (replay/reshard.py)."""
        from r2d2_tpu.replay.snapshot import save_replay, snapshot_topology

        os.makedirs(self.cfg.checkpoint_dir, exist_ok=True)
        path = self._replay_snapshot_path()
        save_replay(
            self.replay, path, extra=extra,
            topology=snapshot_topology(self.replay, tp=self.cfg.tp_size),
        )
        return path

    _RESHARD_LIVE_KEYS = ("dp_size", "tp_size", "fsdp_size", "replay_plane")

    def reshard_live(self, **topology) -> dict:
        """Elastic live reshard: re-split the replay plane across a new
        dp/tp/fsdp topology IN THIS PROCESS — the learner-side half of the
        serve plane's elastic story (serve/autoscale.py): when the fleet
        grows or drains, the learner follows the topology change without a
        process exit and restart.

        Sequence: quiesce (drain every deferred plane write-back) ->
        snapshot the replay through the same atomic writer the preemption
        path uses -> swap the config/mesh/state placement to the new
        topology -> rebuild the replay plane -> regather + re-deal the
        snapshot slabs across the new layout (replay/reshard.py) ->
        rebind the live actor's replay hooks. The actor object itself is
        untouched — its RNG streams, env state, and param store carry
        straight through — and the replay contents round-trip through the
        lossless snapshot/reshard path, so the resumed run is bit-exact
        with one that never resharded (tests/test_autoscale.py proves it).

        Accepts only the topology knobs (`dp_size`, `tp_size`,
        `fsdp_size`, `replay_plane`). Single-process only: the multihost
        plane reshards through the exit/resume path (reshard_on_resume),
        where every process re-reads the shared snapshot set. The
        snapshot file is left in place — it is the crash-safety artifact
        until the next one overwrites it. Returns a summary dict."""
        unknown = set(topology) - set(self._RESHARD_LIVE_KEYS)
        if unknown:
            raise ValueError(
                f"reshard_live accepts {self._RESHARD_LIVE_KEYS}, "
                f"got {sorted(unknown)}"
            )
        if (
            jax.process_count() > 1
            or self.cfg.replay_plane == "multihost"
            or topology.get("replay_plane") == "multihost"
        ):
            raise NotImplementedError(
                "live reshard is single-process; multihost topologies "
                "reshard through exit + resume (cfg.reshard_on_resume)"
            )
        from r2d2_tpu.replay.reshard import reshard_replay, snapshot_paths

        # 1. quiesce: every in-flight priority write-back must land in the
        #    slabs before they are snapshotted
        self.finish_updates()
        snap = self.save_replay_snapshot()
        before_env_steps = self.replay.env_steps
        before_size = len(self.replay)
        # 2. swap the topology: new config, new mesh, state re-placed the
        #    same way __init__ places it (values untouched -> bit-exact)
        cfg = self.cfg.replace(**topology).validate()
        self.cfg = cfg
        self.mesh = None
        if cfg.dp_size * cfg.tp_size * cfg.fsdp_size > 1:
            n_mesh = cfg.dp_size * cfg.tp_size * cfg.fsdp_size
            self.mesh = make_mesh(dp=cfg.dp_size, tp=cfg.tp_size,
                                  devices=jax.devices()[:n_mesh],
                                  fsdp=cfg.fsdp_size)
        state_host = jax.device_get(self.state)
        if self.mesh is not None:
            from r2d2_tpu.parallel.mesh import train_state_shardings

            self.state = jax.device_put(
                state_host, train_state_shardings(state_host, self.mesh)
            )
        else:
            self.state = jax.device_put(state_host)
        # 3. rebuild the plane (its jitted steps re-trace against the new
        #    mesh) and re-deal the snapshot across the new layout
        self.plane = _PLANES[cfg.replay_plane](self)
        self.replay = self.plane.replay
        self._resume_carry = reshard_replay(
            self.replay, snapshot_paths(cfg.checkpoint_dir)
        )
        # env_steps_offset is unchanged: the restored counter equals the
        # pre-reshard one, so the global total carries straight through
        # 4. rebind the actor's replay hooks — the ONLY replay references
        #    living outside the plane
        if hasattr(self.actor, "push_block"):
            self.actor.push_block = self.replay.add_block
        if hasattr(self.actor, "replay"):
            self.actor.replay = self.replay
        return {
            "snapshot": snap,
            "replay_plane": cfg.replay_plane,
            "dp_size": cfg.dp_size,
            "tp_size": cfg.tp_size,
            "fsdp_size": cfg.fsdp_size,
            "env_steps": self.replay.env_steps,
            "env_steps_before": before_env_steps,
            "replay_size": len(self.replay),
            "replay_size_before": before_size,
        }

    def _snapshot_async(self) -> None:
        """Periodic (snapshot_every) snapshot off the hot path: the write
        runs on a background thread; if the previous one is still going it
        is simply skipped (next crossing tries again). The write itself is
        atomic (tmp+rename), so the previous snapshot stays valid until
        the new one fully lands."""
        if self._snap_thread is not None and self._snap_thread.is_alive():
            return
        t = threading.Thread(
            target=self._snapshot_on_exit, name="replay-snapshot", daemon=True
        )
        self._snap_thread = t
        t.start()

    def _snapshot_on_exit(self, extra: Optional[dict] = None) -> None:
        """finally-block wrapper: the snapshot is the largest write of the
        run (obs-store-sized), so a failure here (ENOSPC) must not replace
        the in-flight training exception with its own."""
        t = self._snap_thread
        if t is not None and t is not threading.current_thread() and t.is_alive():
            # a periodic snapshot is mid-write: let it land (its rename and
            # ours would race on the same final path otherwise)
            t.join(timeout=60.0)
        try:
            self.save_replay_snapshot(extra=extra)
        except Exception as e:  # noqa: BLE001 — log-and-continue on exit
            import traceback

            print(f"replay snapshot failed on exit: {e!r}")
            traceback.print_exc()

    def _stop_profile(self) -> None:
        """Finalize an in-flight trace; safe to call repeatedly. Run modes
        call this on every exit path so a crash or an early end of training
        cannot lose the requested trace."""
        if self._profile_active:
            jax.block_until_ready(self.state.params)
            profiling.stop_trace()
            self._profile_active = False
            self._profile_remaining = 0

    def _log(self, m, step, extra: Optional[dict] = None):
        """Queue this update's metrics WITHOUT materializing them.

        float(m["loss"]) on a live device handle is a full stream sync —
        paid once per update, it re-serializes the pipeline every dispatch
        ("async dispatch tax"). Instead: start the device->host copies
        async, remember the LATEST (m, step, extra), and materialize only
        when the log cadence fires (cfg.log_interval seconds) or at a cut
        point (finish_updates, snapshot crossings, run-mode exit — via
        _flush_log). Updates between cadence firings are never fetched:
        the metrics jsonl samples the update stream at the log cadence
        rather than recording every update (episode stats still aggregate
        exactly — pop_episode_stats moves to emit time)."""
        for v in (m or {}).values():
            copy = getattr(v, "copy_to_host_async", None)
            if copy is not None:
                copy()
        self._pending_metrics = (m, step, extra)
        if time.time() - self._last_log_emit >= self.cfg.log_interval:
            self._flush_log()

    def _dispatch_host_row(self) -> dict:
        """Host ms per fused dispatch since the last metrics row, from the
        span aggregates and counters (the operator's view of
        utils/profiling.SPANS): draw, readback wait, everything but the wait
        by wall and by the thread's CPU clock (the difference is time the
        dispatch thread was not running), the interpreter's collections, and
        the share of priority rows the staleness mask let through. Empty off
        the fused path."""
        now, mark = profiling.counters(), self._span_mark
        self._span_mark = now

        def grown(key: str) -> float:
            return now.get(key, 0) - mark.get(key, 0)

        n = grown("r2d2.dispatch.count")
        if n <= 0:
            return {}

        def ms(name: str, clock: str = "total_ns") -> float:
            return grown(f"{name}.{clock}") / n / 1e6

        wait = ms("r2d2.dispatch.readback")
        row = {
            "host_sample_ms": round(ms("r2d2.replay.sample"), 3),
            "host_readback_ms": round(wait, 3),
            "host_busy_ms": round(ms("r2d2.dispatch") - wait, 3),
            "host_cpu_ms": round(
                ms("r2d2.dispatch", "cpu_ns") - ms("r2d2.dispatch.readback", "cpu_ns"), 3
            ),
            "host_gc_ms": round(ms("r2d2.host.gc"), 3),
        }
        offered = grown("replay.priority_rows_offered")
        if offered:
            # below 100: rows whose slot was overwritten before they came back
            row["priority_applied_pct"] = round(
                100.0 * grown("replay.priority_rows_applied") / offered, 2
            )
        return row

    def _flush_log(self) -> None:
        """Materialize and emit the queued metrics record, if any."""
        pend, self._pending_metrics = self._pending_metrics, None
        if pend is None:
            return
        m, step, extra = pend
        self._last_log_emit = time.time()
        log_extras = getattr(self.plane, "log_extras", None)
        if log_extras is not None:
            extra = {**(extra or {}), **log_extras()}
        retries = total_retries()
        if retries:
            extra = {**(extra or {}), "io_retries": retries}
        extra = {**(extra or {}), **self._dispatch_host_row()}
        n_ep, r_sum = self.replay.pop_episode_stats()
        if self.cfg.replay_plane == "multihost" and jax.process_count() > 1:
            # env_steps_offset is a GLOBAL restored total (the snapshot
            # restore rebases it against the globally-summed restored
            # count), so local + offset would understate — possibly go
            # negative — on a resumed multi-process run. Log the two
            # unambiguous pieces instead; checkpoints carry the true
            # global total via _global_env_steps() (no collective here:
            # logging is per-host and must not require lockstep).
            env_steps = {
                "env_steps_local": self.replay.env_steps,
                "env_steps_offset_global": self.env_steps_offset,
            }
        else:
            env_steps = {"env_steps": self.replay.env_steps + self.env_steps_offset}
        stamp, self.runtime_stamp = self.runtime_stamp, None
        self.metrics.log(
            {
                "step": step,
                **env_steps,
                "replay_size": len(self.replay),
                "loss": float(m["loss"]),
                "q_mean": float(m["q_mean"]),
                "episodes": n_ep,
                "mean_return": (r_sum / n_ep) if n_ep else None,
                **(extra or {}),
                # first record only: what the run is on (utils/runtime.py)
                **(stamp or {}),
            }
        )

    # ---------------------------------------------------------------- modes

    @spanned("r2d2.setup.ring_fill")
    def warmup(
        self, max_steps: Optional[int] = None, beat: Optional[Callable[[], None]] = None
    ) -> None:
        """Collect until sampling opens (reference worker.py:150).
        `beat` (e.g. Supervisor.main_beat) is stamped between collection
        steps so an armed watchdog covers the warmup phase too.

        Stall guard: batched ring writes shrink effective capacity to
        floor(num_blocks/E)*E slots (ReplayControlPlane._reserve_contiguous
        retires the tail), and episode-aligned chunks store fewer than
        block_length transitions per slot — so a learning_starts that
        exceeds what the ring can actually hold would loop here forever.
        The guard counts RECORDED insertions (replay.env_steps delta, not
        attempted env steps — episode-aligned chunks record only a
        fraction of attempts): once enough transitions to fill the ring
        twice over have been inserted without sampling opening, the replay
        has provably saturated below learning_starts — raise instead of
        spinning."""
        steps = 0
        inserted0 = last_inserted = self.replay.env_steps
        progress_mark = 0  # attempted steps at the last recorded insertion
        saturation = 2 * self.cfg.buffer_capacity + self.cfg.learning_starts
        while not self.replay.can_sample():
            # single-process only: warmup iterations are NOT in lockstep
            # across hosts (each fills at its own rate), so the allgather
            # handshake _preempt_now uses would deadlock here. Multi-host
            # preemption during warmup falls through to the run loop's
            # first iteration check instead.
            if jax.process_count() == 1 and self._preempt.is_set():
                self.preempted = True
                return
            self.actor.step()
            if beat is not None:
                beat()
            steps += self.actor.steps_per_call
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError("warmup exceeded max_steps without filling replay")
            if self.replay.env_steps != last_inserted:
                last_inserted = self.replay.env_steps
                progress_mark = steps
            if self.replay.env_steps - inserted0 >= saturation:
                raise RuntimeError(
                    f"replay saturated at {len(self.replay)} transitions, below "
                    f"learning_starts={self.cfg.learning_starts}: the ring's "
                    "effective capacity (tail retirement for batched writes, "
                    "short-episode blocks) cannot reach the sampling gate — "
                    "lower learning_starts or grow buffer_capacity"
                )
            if steps - progress_mark >= saturation:
                # termination backstop: recording has STALLED (a whole
                # saturation-window of attempted env steps with zero
                # insertions, e.g. an env whose episodes never complete a
                # chunk) — the recorded-insertion guard above would never
                # fire, so raise here instead of spinning forever
                raise RuntimeError(
                    f"warmup recorded no insertions over {saturation} attempted "
                    f"env steps (replay stuck at {len(self.replay)} transitions): "
                    "episodes may never complete within the collector's chunks — "
                    "check max_episode_steps vs chunk/block length"
                )

    def reset_clock(self) -> None:
        """(Re)start the wall-minutes clock that the checkpoint cadence
        stamps (_cadences / _finalize_preempt). Run modes call this on
        entry; external drivers that act as their own run mode (the live
        loop) call it too instead of poking _start_time directly."""
        self._start_time = time.time()

    def run_inline(self, env_steps_per_update: Optional[int] = None) -> None:
        """Strict alternation: k env steps, one update (SURVEY.md 7.2)."""
        cfg = self.cfg
        self.reset_clock()
        k = env_steps_per_update or max(cfg.num_actors, 1)
        # one dispatch is steps_per_update learner updates: scale collection
        # so the env-step : update ratio the caller asked for is preserved
        k *= self.plane.steps_per_update
        # single-threaded loop: the main-thread watchdog is the only stall
        # protection (utils/supervision.py — hard-exits a wedged process)
        sup = self._sup = self._make_supervisor()
        with self._sigterm_to_preempt(), sup.armed_watchdog():
            self.warmup(beat=sup.main_beat)
            try:
                while self._step < cfg.training_steps:
                    sup.main_beat()
                    if self._preempt_now():
                        break
                    for _ in range(max(k // self.actor.steps_per_call, 1)):
                        self.actor.step()
                    m, step = self._one_update(self.plane.sample())
                    self._log(m, step)
            finally:
                # watchdog off before the drain: cleanup must not count as
                # a stall
                sup.stop.set()
                self._stop_profile()
                # carry BEFORE finish_updates: capture_pending serializes
                # the deferred write-back that the drain would apply
                carry = self._capture_carry_safe()
                self.finish_updates()
                if cfg.snapshot_replay:
                    self._snapshot_on_exit(extra=carry)
        if self.preempted:
            self._finalize_preempt()

    def run_threaded(self) -> None:
        """Actor thread + prefetch thread + learner loop (reference
        worker.py:110-175,364-371 collapsed into shared memory). Worker
        threads run under a Supervisor (utils/supervision.py): a crashed
        actor/sampler iteration is restarted with the traceback recorded
        instead of silently starving the learner (SURVEY.md section 5.3)."""
        cfg = self.cfg
        self.reset_clock()
        batch_q: "queue.Queue" = queue.Queue(maxsize=8)
        sup = self._sup = self._make_supervisor()
        with self._sigterm_to_preempt(), sup.armed_watchdog():
            self._run_threaded_body(sup, batch_q)
        if self.preempted:
            self._finalize_preempt()

    def _make_supervisor(self) -> Supervisor:
        return Supervisor(
            heartbeat_timeout=self.cfg.heartbeat_timeout,
            stall_fatal_timeout=self.cfg.stall_fatal_timeout,
        )

    def disarm_watchdog(self) -> None:
        """For library callers that catch WorkerStalledError and keep the
        process alive: the watchdog deliberately survives that unwind (it
        guards against atexit hangs on the wedged backend), so it must be
        disarmed explicitly before doing anything long-running."""
        if getattr(self, "_sup", None) is not None:
            self._sup.disarm()

    def _run_threaded_body(self, sup: Supervisor, batch_q: "queue.Queue") -> None:
        cfg = self.cfg
        # armed BEFORE warmup (caller holds armed_watchdog): the warmup
        # collection loop runs on the main thread against the same backend
        # the watchdog guards
        self.warmup(beat=sup.main_beat)

        spi = cfg.samples_per_insert
        # THIS-RUN, THIS-HOST accounting: inserts baseline at the current
        # counter (a restored replay snapshot's lifetime total must not
        # starve collection), and a multi-process run divides the global
        # batch by process count so the ratio compares host-local apples
        consumed_per_update = cfg.batch_size * cfg.learning_steps / max(jax.process_count(), 1)
        inserted0 = self.replay.env_steps

        def actor_body():
            if spi > 0 and self.replay.can_sample():
                consumed = (self._step - self._initial_step) * consumed_per_update
                inserted = max(self.replay.env_steps - inserted0, 1)
                if consumed / inserted < spi:
                    # data is plentiful relative to optimization: yield the
                    # device to the learner (bounded sleep keeps the
                    # supervisor heartbeat fresh)
                    time.sleep(0.05)
                    return
            self.actor.step()

        # one sample + one bounded put attempt per call: a full queue (the
        # learner compiling or checkpointing) retries across calls, keeping
        # the heartbeat fresh instead of looking like a stall
        pending = [None]

        def sampler_body():
            if pending[0] is None:
                # pipelined: the host and tiered planes copy the batch out at
                # sample time, so a queued item cannot be invalidated by a
                # concurrent block write; the HBM planes queue a token and
                # draw when the update dispatches
                pending[0] = self.plane.sample(pipelined=True)
            try:
                batch_q.put(pending[0], timeout=0.5)
                pending[0] = None
            except queue.Full:
                pass

        def sampler_recover():
            pending[0] = None  # a half-built item may be inconsistent

        sup.spawn("actor", actor_body, max_restarts=cfg.worker_max_restarts,
                  on_restart=self.actor.resync)
        sup.spawn("sampler", sampler_body, max_restarts=cfg.worker_max_restarts,
                  on_restart=sampler_recover)
        last_health: Optional[dict] = None

        def cleanup():
            # shutdown FIRST: it stops the main-thread watchdog, whose
            # timeout must not count the (possibly minutes-long) priority
            # drain and replay snapshot below as a "stall"; it also joins
            # the actor/sampler threads, so the carry below sees quiescent
            # accumulators and a frozen replay
            sup.shutdown()
            self._stop_profile()
            carry = self._capture_carry_safe()
            self.finish_updates()
            if cfg.snapshot_replay:
                self._snapshot_on_exit(extra=carry)

        try:
            while self._step < cfg.training_steps:
                sup.main_beat()
                if self._preempt_now():
                    break
                try:
                    item = batch_q.get(timeout=2.0)
                except queue.Empty:
                    # raises WorkerFatalError on a dead worker; stall/restart
                    # transitions still reach the metrics stream even though
                    # no update is flowing (that is exactly when they matter)
                    stats = sup.check()
                    if stats != last_health:
                        last_health = stats
                        self.metrics.log({"step": self._step, **stats})
                    continue
                m, step = self._one_update(item)
                health = sup.check()
                last_health = health
                self._log(m, step, extra=health)
        except WorkerStalledError:
            # a wedged worker means the backend itself is suspect: any
            # cleanup that blocks on device work (priority drain, profile
            # sync, replay snapshot) would hang the very exit this error
            # exists to force — skip it ALL, including Supervisor.shutdown
            # (which would stop the main-thread watchdog: it must stay
            # armed so a hang in interpreter-shutdown atexit hooks still
            # gets hard-exited). Worker threads are daemons; the process
            # is going down either way.
            raise
        except BaseException:
            cleanup()
            raise
        else:
            cleanup()

    def run_fused(self, collect_every: Optional[int] = None) -> None:
        """Fused actor-learner loop: ONE dispatch per iteration runs K
        updates plus (every collect_every'th dispatch) a full collection
        chunk and its store scatter (megastep.py). No worker threads: the
        host only does sum-tree bookkeeping between dispatches.

        collect_every=None paces collection from cfg.samples_per_insert on
        ACTUAL consumed/inserted counters (the threaded pacer's rule);
        samples_per_insert == 0 collects every dispatch. An explicit
        collect_every overrides both."""
        cfg = self.cfg
        if cfg.collector != "device" or cfg.replay_plane not in (
            "device", "sharded", "multihost"
        ):
            raise ValueError(
                "run_fused needs collector='device' and replay_plane="
                f"'device'/'sharded'/'multihost' (got {cfg.collector!r}, "
                f"{cfg.replay_plane!r})"
            )
        self.reset_clock()
        # main-thread watchdog: this loop has no worker threads, so a
        # wedged device readback would hang it silently forever — the
        # watchdog hard-exits (utils/supervision.STALL_EXIT_CODE) instead.
        # Armed before warmup so the warmup collection is covered too.
        sup = self._sup = self._make_supervisor()
        with self._sigterm_to_preempt(), sup.armed_watchdog():
            self._run_fused_body(sup, collect_every)
        if self.preempted:
            self._finalize_preempt()

    def _run_fused_body(self, sup: Supervisor, collect_every: Optional[int]) -> None:
        cfg = self.cfg
        from r2d2_tpu.megastep import (
            FusedSystemRunner,
            MultiHostFusedRunner,
            ShardedFusedRunner,
        )

        self.warmup(beat=sup.main_beat)
        common = dict(
            collect_every=1 if collect_every is None else collect_every,
            chunk_len=self.actor.chunk,
            sample_rng=self.sample_rng,
            samples_per_insert=cfg.samples_per_insert if collect_every is None else 0.0,
        )
        if cfg.replay_plane == "multihost":
            # collective megastep over the GLOBAL mesh: the runner builds
            # its own per-local-shard env slots (pinned-slot rule); the
            # warmup collector's episodes end here
            runner = MultiHostFusedRunner(
                cfg, self.net, self.fn_env, self.replay,
                self.actor.epsilons, self.actor.key, self.mesh, **common,
            )
        elif cfg.replay_plane == "sharded":
            runner = ShardedFusedRunner(
                cfg, self.net, self.fn_env, self.replay,
                self.actor.epsilons, self.actor.env_state, self.actor.key,
                self.mesh, **common,
            )
        else:
            runner = FusedSystemRunner(
                cfg, self.net, self.fn_env, self.replay,
                self.actor.epsilons, self.actor.env_state, self.actor.key,
                **common,
            )
        try:
            # metrics log lags ONE dispatch: reading a dispatch's loss
            # floats immediately would sync on it, re-serializing the very
            # readback the runner's deferred-drain protocol pipelines away
            # — a previous dispatch's floats have already landed
            pending_log = None
            while self._step < cfg.training_steps:
                sup.main_beat()
                if self._preempt_now():
                    break
                self._profile_gate()
                prev = self._step
                with step_span("r2d2.step.megastep", prev):
                    self.state, m, recorded = runner.step(self.state)
                self._step += cfg.updates_per_dispatch
                self._profile_tick(cfg.updates_per_dispatch)
                self._cadences(prev, self._step)
                # log on drain dispatches (a chunk's accounting landed):
                # same cadence class as the old collect-dispatch logging
                if recorded and pending_log is not None:
                    self._log(*pending_log)
                pending_log = (m, self._step)
        finally:
            # watchdog off before the drain: cleanup must not count as a stall
            sup.stop.set()
            self._stop_profile()
            runner.finish()
            # the deferred metrics of the final dispatch have landed by now
            if pending_log is not None:
                self._log(*pending_log)
            self._flush_log()
            # hand the collector loop state back so a later warmup/eval on
            # this Trainer continues from consistent episodes (the sharded
            # runner keeps one PRNG stream per shard; shard 0's continues
            # the actor's single stream)
            self.actor.env_state = runner.env_state
            self.actor.key = runner.key if hasattr(runner, "key") else runner.keys[0]
            self.actor.total_steps += runner.total_env_steps
            if cfg.snapshot_replay:
                # carry AFTER the actor handback so the DeviceCollector
                # carry captures the runner's final env/PRNG state
                self._snapshot_on_exit(extra=self._capture_carry_safe())


def _replay_core_name(cfg: R2D2Config) -> str:
    """Which host replay core the control plane got: the C++ one, or
    numpy (opted out, or the build fell back — _native says why)."""
    from r2d2_tpu._native import load_native

    native = cfg.use_native_replay and load_native() is not None
    return "native" if native else "numpy"


def main(argv=None):
    p = argparse.ArgumentParser(description="r2d2_tpu trainer")
    p.add_argument("--preset", default="atari", choices=sorted(PRESETS))
    p.add_argument("--env", default=None, help="override env name (e.g. catch)")
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--mode", default="threaded", choices=["threaded", "inline", "fused"],
                   help="fused: one dispatch = K updates + collection chunk "
                        "(collector='device' + replay 'device' only)")
    p.add_argument("--replay", default=None,
                   choices=["host", "tiered", "device", "sharded", "multihost"],
                   help="replay data plane (default: preset's replay_plane)")
    p.add_argument("--distributed", action="store_true",
                   help="initialize jax.distributed from the standard env "
                        "vars (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES "
                        "/ JAX_PROCESS_ID) before building the trainer; "
                        "pair with --replay multihost")
    p.add_argument("--collector", default=None, choices=["host", "device"],
                   help="experience collection: host actor loop or fully "
                        "on-device jitted chunks (pure-JAX envs only)")
    p.add_argument("--updates-per-dispatch", type=int, default=None,
                   help="fold K learner updates into one jitted dispatch "
                        "(device replay plane; amortizes launch latency)")
    p.add_argument("--dp", type=int, default=None,
                   help="data-parallel mesh size (overrides preset dp_size)")
    p.add_argument("--tp", type=int, default=None,
                   help="tensor-parallel mesh size (overrides preset tp_size)")
    p.add_argument("--fsdp", type=int, default=None,
                   help="fsdp mesh-axis size (overrides preset fsdp_size): "
                        "shards the Adam mu/nu trees over a third mesh axis "
                        "(parallel/sharding_map.py); replay snapshots are "
                        "fsdp-agnostic, so --resume/--reshard compose freely")
    p.add_argument("--model-preset", default=None,
                   help="named model-size preset (config.MODEL_PRESETS: "
                        "wide/xl widen the LSTM, deep/deep_wide add encoder "
                        "Dense layers) applied over the run preset; "
                        "--set still wins on individual fields")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--reshard", action="store_true",
                   help="on --resume, a replay snapshot saved under a "
                        "different (dp, tp, process_count) topology is "
                        "regathered and re-split across the current layout "
                        "(replay/reshard.py) instead of aborting with "
                        "TopologyMismatch")
    p.add_argument("--snapshot-replay", action="store_true",
                   help="save full replay contents at end of run and restore "
                        "them on --resume (replay/snapshot.py)")
    p.add_argument("--metrics", default=None)
    p.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                   help="override any R2D2Config field, typed by the field "
                        "(repeatable; e.g. --set gamma=0.99 --set "
                        "batch_size=32 --set obs_shape=64,64,3)")
    p.add_argument("--profile-dir", default=None,
                   help="record a jax.profiler trace of the first post-warmup updates "
                        "(Python tracer off; host spans r2d2.<layer>.<phase>, device "
                        "scopes r2d2_<region>: utils/profiling.SPANS)")
    p.add_argument("--profile-steps", type=int, default=20)
    p.add_argument("--profile-port", type=int, default=0,
                   help="if set, start a live profiler server on this port")
    args = p.parse_args(argv)

    if args.distributed:
        from r2d2_tpu.parallel.multihost import initialize_distributed

        initialize_distributed()

    cfg = PRESETS[args.preset]()
    if args.model_preset:
        from r2d2_tpu.config import apply_model_preset

        cfg = apply_model_preset(cfg, args.model_preset)
    overrides = {}
    if args.env:
        overrides["env_name"] = args.env
    if args.steps:
        overrides["training_steps"] = args.steps
    if args.metrics:
        overrides["metrics_path"] = args.metrics
    if args.replay:
        overrides["replay_plane"] = args.replay
    if args.mode == "fused" and args.collector is None:
        args.collector = "device"  # the only collector run_fused supports
    if args.collector:
        overrides["collector"] = args.collector
        if args.collector == "device" and args.replay is None:
            overrides["replay_plane"] = "device"
    if args.snapshot_replay:
        overrides["snapshot_replay"] = True
    if args.reshard:
        overrides["reshard_on_resume"] = True
    if args.dp is not None:
        overrides["dp_size"] = args.dp
    if args.tp is not None:
        overrides["tp_size"] = args.tp
    if args.fsdp is not None:
        overrides["fsdp_size"] = args.fsdp
    if args.updates_per_dispatch is not None:
        overrides["updates_per_dispatch"] = args.updates_per_dispatch
        # convenience only for the single-chip default: never silently
        # replace an explicitly-chosen or preset sharded/device plane —
        # config.validate() surfaces incompatible combinations instead
        if (
            args.updates_per_dispatch > 1
            and args.replay is None
            and args.collector != "device"
            and cfg.replay_plane == "host"
        ):
            overrides["replay_plane"] = "device"
    if args.set:
        # applied LAST: --set is the explicit word on any field
        overrides.update(parse_overrides(args.set))
    if overrides:
        cfg = cfg.replace(**overrides)

    if args.profile_port:
        start_profiler_server(args.profile_port)
    # deterministic fault injection for chaos drills (R2D2_FAULTS env var;
    # utils/faults.py) — a no-op when unset
    install_from_env()
    trainer = Trainer(
        cfg,
        resume=args.resume,
        profile_dir=args.profile_dir,
        profile_steps=args.profile_steps,
    )
    from r2d2_tpu.utils.runtime import describe_placement, print_runtime_banner

    # start_step says what --resume actually restored (0: found nothing)
    trainer.runtime_stamp = print_runtime_banner(
        "train", trainer.cfg,
        replay_core=_replay_core_name(trainer.cfg),
        start_step=trainer._initial_step,
    )
    try:
        if args.mode == "inline":
            trainer.run_inline()
        elif args.mode == "fused":
            trainer.run_fused()
        else:
            trainer.run_threaded()
    except WorkerStalledError as e:
        # CLI contract: a wedged runtime exits with STALL_EXIT_CODE so an
        # external supervisor can distinguish "restart with --resume" from
        # an ordinary crash. (Library callers instead receive the
        # exception; if they keep the process alive they must disarm via
        # Trainer.disarm_watchdog or e.supervisor.disarm().)
        from r2d2_tpu.utils.supervision import exit_for_stall

        exit_for_stall(e)
    from r2d2_tpu.utils.compilation_cache import log_compile_cache_stats

    log_compile_cache_stats()
    if trainer.mesh is not None:
        print("[placement] " + json.dumps(describe_placement(
            params=trainer.state.params,
            replay=getattr(trainer.replay, "stores", {}),
        )), flush=True)
    if trainer.preempted:
        # CLI contract: SIGTERM was absorbed into a clean cut — replay
        # snapshot + mid-run carry + finalized checkpoint are on disk.
        # PREEMPT_EXIT_CODE tells the external supervisor "restart with --resume
        # and training continues bit-exactly", vs STALL_EXIT_CODE's
        # "state may be stale".
        sys.exit(PREEMPT_EXIT_CODE)


if __name__ == "__main__":
    main()
