"""Multi-chip serving: replicated serve stacks with session affinity.

The SEED RL shape (Espeholt et al. 2020) at the chip level: one
`PolicyServer` per local device — each with its OWN micro-batcher, session
cache (plus host spill tier), jitted step, and supervised serve loop — and
a `SessionRouter` in front that pins every session to exactly one replica.
A session's recurrent carry lives on exactly one device, so routing a
request anywhere else would silently restart the session from zero state;
affinity is therefore correctness, not just locality.

Routing rules (documented in ARCHITECTURE.md):

- a session already mapped goes to its mapped replica, always;
- a NEW session goes to the least-loaded replica (by tracked session
  count), tie-broken by a stable hash (crc32 of the session id) so equal
  loads still spread deterministically;
- the affinity map is itself LRU-bounded to the total session capacity of
  the fleet (HBM rows + spill rows per replica): a session old enough to
  fall out of the map has necessarily also aged out of its replica's cache
  AND slab, so re-hashing it elsewhere loses nothing.

Each replica keeps the compile-once-per-bucket property independently (its
jitted step is specialized to its own device; `trace_count` per replica
stays <= len(buckets)), and under config.serve_pipeline each replica's own
`start()` spawns its depth-2 pipeline pair — "serve-loop-<name>" staging
and dispatching, "serve-complete-<name>" materializing results — so the
fleet overlaps host staging with device steps on every chip independently;
the fleet stats() sums the per-replica `completed_batches` /
`metrics_skipped` counters alongside the batch counters. Hot reload is
published to ALL replicas under one
shared version number inside one critical section: the checkpoint is
restored ONCE on host, then `PolicyServer.publish` runs per replica
(re-quantizing per replica under serve_quantization="int8" and placing
params on that replica's device) — each replica's swap is a single atomic
attribute write, and no two reloads interleave, so replicas can never end
up on different versions once a reload returns.
"""

from __future__ import annotations

import threading
import time
import zlib
from collections import OrderedDict
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from r2d2_tpu.config import R2D2Config
from r2d2_tpu.learner import init_train_state
from r2d2_tpu.serve.server import PolicyServer, ServeConfig
from r2d2_tpu.utils.checkpoint import latest_checkpoint_step, restore_checkpoint
from r2d2_tpu.utils.faults import Backoff, InjectedFault, fault_point
from r2d2_tpu.utils.metrics import MetricsLogger
from r2d2_tpu.utils.supervision import Supervisor


class SessionRouter:
    """Session -> replica affinity with least-loaded placement for new
    sessions. Thread-safe: any client thread may route concurrently."""

    def __init__(self, n_replicas: int, max_tracked: int = 0):
        if n_replicas < 1:
            raise ValueError("n_replicas must be >= 1")
        self.n_replicas = n_replicas
        # 0 = unbounded; otherwise LRU-drop the stalest affinity once the
        # map outgrows the fleet's total session capacity (see module doc)
        self.max_tracked = max_tracked
        # per-ACTIVE-replica share of the bound: the fleet's session
        # capacity changes when the autoscaler grows or drains the fleet,
        # so the bound is recomputed from this share on every activate /
        # deactivate / add_slot instead of frozen at construction size
        self._per_replica = max_tracked // n_replicas if max_tracked else 0
        self._map: "OrderedDict[str, int]" = OrderedDict()
        self._counts = [0] * n_replicas
        # chaos plane: a killed replica is deactivated, never removed —
        # indices stay stable, and route() treats its sessions as new
        # placements among the survivors (the migration path re-assigns
        # them explicitly first, so only un-migrated stragglers re-place)
        self._active = [True] * n_replicas
        self._lock = threading.Lock()
        self.routed = 0      # total route() calls
        self.new_routes = 0  # sessions placed for the first time
        self.dropped = 0     # affinities LRU-dropped from the map
        self.reroutes = 0    # affinities moved off a deactivated replica

    def _recompute_bound(self) -> None:
        # caller holds self._lock. 0 stays unbounded forever.
        if self._per_replica:
            self.max_tracked = self._per_replica * max(sum(self._active), 1)

    def _trim(self) -> None:  # r2d2: guarded-by(_lock)
        # caller holds self._lock: LRU-drop down to the (possibly just
        # shrunk) bound — a dropped session's capacity left the fleet
        # with the replica that owned it (module-doc argument)
        while self.max_tracked and len(self._map) > self.max_tracked:
            _, old_replica = self._map.popitem(last=False)
            self._counts[old_replica] -= 1
            self.dropped += 1

    def route(self, session_id: str) -> int:
        """The replica index this session's requests must go to."""
        with self._lock:
            replica = self._map.get(session_id)
            if replica is not None and not self._active[replica]:
                # mapped to a dead replica and not migrated: place fresh
                del self._map[session_id]
                self._counts[replica] -= 1
                self.reroutes += 1
                replica = None
            if replica is None:
                live = [i for i in range(self.n_replicas) if self._active[i]]
                if not live:
                    raise RuntimeError("no active replicas to route to")
                self.new_routes += 1
                lo = min(self._counts[i] for i in live)
                ties = [i for i in live if self._counts[i] == lo]
                replica = ties[zlib.crc32(session_id.encode()) % len(ties)]
                self._counts[replica] += 1
                self._map[session_id] = replica
                self._trim()
            self._map.move_to_end(session_id)
            self.routed += 1
            return replica

    def deactivate(self, replica: int) -> None:
        """Take a replica out of rotation (kill/drain path). Its existing
        affinities stay mapped until migrated (assign) or re-placed on
        the session's next route(); the LRU bound shrinks with the lost
        capacity (stalest affinities past the new bound are dropped)."""
        with self._lock:
            self._active[replica] = False
            self._recompute_bound()
            self._trim()

    def activate(self, replica: int) -> None:
        """Put a replica (back) into rotation — the inverse of deactivate:
        the scale-up path activates a freshly warmed-and-published replica
        for placement, and the LRU bound grows with the new capacity."""
        with self._lock:
            self._active[replica] = True
            self._recompute_bound()

    def add_slot(self) -> int:
        """Grow the replica set by one INACTIVE slot and return its index.
        Two-step add (add_slot, then activate once the replica is warmed
        and published) so route() can never place a session on a replica
        that is not serving yet."""
        with self._lock:
            self.n_replicas += 1
            self._counts.append(0)
            self._active.append(False)
            return self.n_replicas - 1

    def assign(self, session_id: str, replica: int) -> None:
        """Force a session's affinity (migration): move the mapping to
        `replica`, adjusting both replicas' load counts."""
        with self._lock:
            old = self._map.pop(session_id, None)
            if old is not None:
                self._counts[old] -= 1
            self._map[session_id] = replica
            self._map.move_to_end(session_id)
            self._counts[replica] += 1

    def active(self) -> List[bool]:
        with self._lock:
            return list(self._active)

    def peek(self, session_id: str) -> Optional[int]:
        """The mapped replica, or None — never creates an affinity."""
        with self._lock:
            return self._map.get(session_id)

    def forget(self, session_id: str) -> Optional[int]:
        """Drop a session's affinity (disconnect); returns the replica it
        was on, or None."""
        with self._lock:
            replica = self._map.pop(session_id, None)
            if replica is not None:
                self._counts[replica] -= 1
            return replica

    def counts(self) -> List[int]:
        with self._lock:
            return list(self._counts)

    def stats(self) -> Dict[str, object]:
        with self._lock:
            return {
                "router_sessions": len(self._map),
                "router_counts": list(self._counts),
                "router_active": list(self._active),
                "router_routed": self.routed,
                "router_new_routes": self.new_routes,
                "router_dropped": self.dropped,
                "router_reroutes": self.reroutes,
            }


class MultiDeviceServer:
    """N PolicyServer replicas (one per device) behind a SessionRouter.

    Mirrors the single-server lifecycle — construct, `warmup()`,
    `start()`, `submit()`/client wrappers, `check()`, `stop()` — so
    drivers and the CLI treat either interchangeably. The checkpoint
    watcher lives HERE (replicas start with watch_checkpoints=False): one
    restore per new step, one shared version, published to every replica.
    """

    def __init__(
        self,
        cfg: R2D2Config,
        serve_cfg: ServeConfig = ServeConfig(),
        params=None,
        checkpoint_dir: Optional[str] = None,
        metrics: Optional[MetricsLogger] = None,
        devices: Optional[Sequence] = None,
    ):
        if devices is None:
            local = jax.local_devices()
            if cfg.serve_devices > len(local):
                raise ValueError(
                    f"serve_devices={cfg.serve_devices} but only "
                    f"{len(local)} local devices are visible"
                )
            devices = local[: cfg.serve_devices]
        if len(devices) < 1:
            raise ValueError("need at least one device")
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self.checkpoint_dir = checkpoint_dir
        self.metrics = metrics
        self.devices = tuple(devices)

        # restore ONCE for the whole fleet (replicas are handed raw host
        # params; each publish places/quantizes per device)
        self.net, self._template = init_train_state(
            cfg, jax.random.PRNGKey(serve_cfg.seed)
        )
        ckpt_step = -1
        if params is None:
            if checkpoint_dir is not None and \
                    latest_checkpoint_step(checkpoint_dir) is not None:
                state, _, _ = restore_checkpoint(checkpoint_dir, self._template)
                params, ckpt_step = state.params, int(state.step)
            else:
                params = self._template.params  # fresh init (smoke serving)
        self._params_host = params  # raw (unquantized) host-side params

        # ONE jitted-step cache for the whole fleet: replicas are clones
        # (same config and net architecture; params and session stores are
        # call arguments, not closure state), so a replica added
        # mid-traffic by the autoscaler reuses the fleet's traced and
        # compiled step executables — its warmup is a handful of cache
        # hits, not a trace+compile stall on the serving cores
        self._step_cache: Dict[bool, object] = {}
        self.replicas: List[PolicyServer] = [
            PolicyServer(
                cfg, serve_cfg, params=params, metrics=metrics,
                device=d, name=f"d{i}", step_cache=self._step_cache,
                net=self.net, template=self._template,
            )
            for i, d in enumerate(self.devices)
        ]
        # replicas published version 0 at ckpt_step -1 in their own
        # __init__; re-publish with the restored step so provenance is
        # right from the first batch (version stays 0 — same params)
        self._reload_lock = threading.Lock()
        self._version = 0
        self._ckpt_step = ckpt_step
        if ckpt_step >= 0:
            for r in self.replicas:
                r.publish(params, ckpt_step, version=0)

        per_replica = serve_cfg.cache_capacity + cfg.serve_spill
        self.router = SessionRouter(
            len(self.replicas), max_tracked=per_replica * len(self.replicas)
        )
        # ONE degrade controller for the whole fleet: each replica built
        # its own under cfg.serve_degrade — replace them all with a shared
        # one driving fleet-level actions (set_arm/set_admission fan out),
        # and strip their ownership so only THIS server runs its worker
        self.degrade = None
        self._arm = "full"
        if cfg.serve_degrade:
            from r2d2_tpu.serve.degrade import DegradeConfig, DegradeController

            self.degrade = DegradeController(
                self, DegradeConfig(slo_ms=cfg.serve_degrade_slo_ms)
            )
            for r in self.replicas:
                r.degrade = self.degrade
                r._degrade_owner = False
        self.replicas_killed = 0
        self.replicas_added = 0
        self.sessions_migrated = 0
        self.sessions_lost = 0
        # sessions that re-placed on a survivor before their carry was
        # imported: alive, but restarted from zero state
        self.sessions_restarted = 0
        self.reloads = 0
        self.reload_errors = 0
        self._watch_backoff = Backoff(
            base=serve_cfg.poll_interval_s, factor=2.0,
            max_delay=max(30.0, serve_cfg.poll_interval_s),
        )
        self.supervisor: Optional[Supervisor] = None
        # elastic autoscaler (serve/autoscale.py): its own supervised
        # thread root, started/stopped with the fleet. Default off: no
        # object, no thread, byte-identical static-fleet behavior.
        self.autoscale = None
        if cfg.serve_autoscale:
            from r2d2_tpu.serve.autoscale import Autoscaler

            self.autoscale = Autoscaler(self)

    # ------------------------------------------------------------- serving

    def submit(self, session_id: str, obs, reward: float = 0.0,
               reset: bool = False, epsilon: Optional[float] = None,
               task: int = 0) -> Future:
        """Route to the session's replica (placing a new session on the
        least-loaded one) and enqueue on that replica's batcher."""
        replica = self.router.route(session_id)
        return self.replicas[replica].submit(
            session_id, obs, reward=reward, reset=reset, epsilon=epsilon,
            task=task,
        )

    def replica_for(self, session_id: str) -> Optional[PolicyServer]:
        """The replica currently owning this session (None if unrouted)."""
        idx = self.router.peek(session_id)
        return None if idx is None else self.replicas[idx]

    def reset_session(self, session_id: str) -> None:
        """Zero a session's carry on its owning replica. Affinity is kept:
        reset means 'start the episode over', not 'disconnect'."""
        idx = self.router.peek(session_id)
        if idx is not None:
            self.replicas[idx].reset_session(session_id)

    def evict(self, session_id: str) -> None:
        """Disconnect: free the session everywhere (HBM slot, spill row,
        affinity entry)."""
        idx = self.router.forget(session_id)
        if idx is not None:
            # replica.evict (not cache.evict): the liveloop hooks — the
            # epsilon assignment and the tap's partial block — must be
            # released along with the HBM slot
            self.replicas[idx].evict(session_id)

    # ---------------------------------------------------------- chaos plane

    def kill_replica(self, idx: int) -> Dict[str, int]:
        """Retire one replica and migrate its sessions to the survivors
        through the spill tier. The order is the correctness argument:

        1. deactivate routing — no NEW request can reach the victim;
        2. close its batcher — racing submits fail fast (QueueFullError)
           instead of stranding futures no loop will resolve;
        3. stop its workers — after the join its cache has no writer, so
        4. export_sessions() is a consistent snapshot (every session at
           its last committed carry), and each row is imported into its
           new replica's HOST SPILL SLAB — no survivor HBM resident is
           evicted by a migrant; the carry promotes bit-exactly on the
           session's next request (the spill tier's demote/promote
           round-trip contract, tests/test_serve_spill.py).

        A session whose client re-submitted between (1) and (4) was
        already re-placed fresh by the router — counted `restarted`, not
        migrated (its import is skipped: the survivor owns newer state).
        A row with no spill room left is genuinely `lost`. Returns the
        breakdown; counters accumulate in stats()."""
        victim = self.replicas[idx]
        self.router.deactivate(idx)
        victim.batcher.close()
        victim.stop()
        exported = victim.cache.export_sessions()
        migrated = lost = restarted = 0
        for sid, (h, c, la, lr) in exported.items():
            target = self.router.route(sid)  # least-loaded survivor
            cache = self.replicas[target].cache
            if sid in cache or cache.spilled(sid):
                restarted += 1
                continue
            if cache.import_spilled(sid, h, c, la, lr):
                self.router.assign(sid, target)
                migrated += 1
            else:
                self.router.forget(sid)
                # full disconnect, not just a routing drop: the liveloop
                # hooks are fleet-shared, so a lost session would
                # otherwise strand its ε assignment and an unflushed
                # partial block in the tap accumulator forever
                victim.evict(sid)
                lost += 1
        with self._reload_lock:
            self.replicas_killed += 1
            self.sessions_migrated += migrated
            self.sessions_lost += lost
            self.sessions_restarted += restarted
        return {"migrated": migrated, "lost": lost, "restarted": restarted}

    def _pick_device(self):
        """A free local device if one exists; otherwise replicas share
        round-robin (CPU fleets and tests co-locate replicas per device)."""
        local = jax.local_devices()
        free = [d for d in local if d not in self.devices]
        if free:
            return free[0]
        return local[len(self.replicas) % len(local)]

    def add_replica(self, device=None) -> int:
        """Grow the fleet by one replica — the autoscaler's scale-up verb,
        also callable directly. The new replica joins the SAME lifecycle
        the fleet was constructed with, in an order that keeps both the
        routing and the publish invariants:

        1. construct with the fleet's raw host params and adopt the shared
           fleet controller/liveloop hooks (never its own worker);
        2. warmup() — every bucket compiles and the staging buffers
           preallocate BEFORE any traffic can reach it;
        3. start its workers (when the fleet is running) while the router
           still has no slot for it — an idle serve loop on an empty
           queue;
        4. adopt it under the single fleet publish: stage its device copy
           of the current (params, step, arm) outside the reload lock,
           then install at the fleet's shared version AND activate its
           router slot inside one critical section — re-staging if a
           reload/arm-switch won the race — so there is no window where
           the replica serves params at a version the fleet has moved
           past, and no routed request before the install.

        Returns the new replica's index. Single-writer contract: scale
        events are serialized by the caller (the autoscaler worker)."""
        if device is None:
            device = self._pick_device()
        replica = PolicyServer(
            self.cfg, self.serve_cfg, params=self._params_host,
            metrics=self.metrics, device=device,
            name=f"d{len(self.replicas)}", step_cache=self._step_cache,
            net=self.net, template=self._template,
        )
        if self.degrade is not None:
            # shared fleet controller, never a second evaluation worker
            replica.degrade = self.degrade
            replica._degrade_owner = False
        r0 = self.replicas[0]
        if r0.tap is not None:
            # liveloop hooks are fleet-shared single instances (loop.py
            # installs them on every replica at attach time; a replica
            # born later inherits them here)
            replica.tap = r0.tap
        if r0.eps_assigner is not None:
            replica.eps_assigner = r0.eps_assigner
        if self.autoscale is not None:
            # wire its completion latencies into the autoscaler's window
            # (no-op when that window is the shared degrade ladder's)
            self.autoscale.attach(replica)
        replica.warmup()
        if self.supervisor is not None:
            replica.start(watch_checkpoints=False)
        slot = self.router.add_slot()
        while True:
            with self._reload_lock:
                raw, step, version, arm = (
                    self._params_host, self._ckpt_step, self._version,
                    self._arm,
                )
            prepared = replica.prepare_for_publish(raw, arm)
            with self._reload_lock:
                if (self._version, self._ckpt_step, self._arm) != (
                    version, step, arm,
                ):
                    continue  # a reload/arm switch landed mid-stage
                replica.install_prepared(
                    prepared, step, version=version, raw_params=raw,
                )
                if len(self.replicas) == slot:
                    self.replicas.append(replica)
                    self.devices = self.devices + (device,)
                self.replicas_added += 1
                # activation inside the same critical section: from the
                # first routed request onward the replica is part of every
                # fleet-wide publish iteration (reload_now / set_arm skip
                # inactive replicas, so activating later would open a
                # version-skew window)
                self.router.activate(slot)
            return slot

    # ------------------------------------------------------ degrade surface
    # (mirrors PolicyServer's so serve/degrade.py drives either; actions
    # fan out to the surviving replicas)

    @property
    def queue_bound(self) -> int:
        # per-replica bound: the ladder reacts to the most pressured
        # replica, not the fleet aggregate a straggler hides inside
        return self.serve_cfg.queue_depth

    def active_replicas(self) -> int:
        """Replicas currently taking routed traffic (the autoscaler's
        fleet-size signal; killed/not-yet-activated slots excluded)."""
        return sum(1 for a in self.router.active() if a)

    def queue_depth(self) -> int:
        return max(
            (r.queue_depth() for r, a in
             zip(self.replicas, self.router.active()) if a),
            default=0,
        )

    def set_admission(self, limit: Optional[int], budget: int = 0) -> None:
        """Install the admission watermark on every live replica (the
        limit and shed budget are per replica — each batcher's queue is
        its own overload domain)."""
        for r, a in zip(self.replicas, self.router.active()):
            if a:
                r.set_admission(limit, budget=budget)

    def shed_spill(self, keep_fraction: float) -> int:
        return sum(
            r.shed_spill(keep_fraction)
            for r, a in zip(self.replicas, self.router.active()) if a
        )

    def set_arm(self, arm: str, params=None) -> bool:
        """Fleet arm switch: stage every live replica's re-prepared params
        OUTSIDE the reload lock (quantize/cast + per-device H2D), then
        install all under one shared version — same lockstep discipline
        as reload_now, so no two replicas serve different arms after this
        returns."""
        if arm == self._arm:
            return False
        raw = self._params_host if params is None else params
        alive = [r for r, a in zip(self.replicas, self.router.active()) if a]
        staged = [r.prepare_for_publish(raw, arm) for r in alive]
        with self._reload_lock:
            version = self._version + 1
            for r, prepared in zip(alive, staged):
                r.install_prepared(prepared, self._ckpt_step, version=version)
                r.arm_switches += 1
            self._version = version
            self._arm = arm
        return True

    # ----------------------------------------------------------- hot reload

    def reload_now(self) -> bool:
        """One reload check for the whole fleet: restore the latest step
        once, stage every replica's device copy OUTSIDE the reload lock
        (the per-device quantize + H2D transfer is the slow part — doing
        it inside the critical section would stall serving fleet-wide for
        N device transfers), then install all replicas under one shared
        version inside one O(N) critical section. Returns True if new
        params went live."""
        fault_point("serve.reload")
        step = latest_checkpoint_step(self.checkpoint_dir)
        if step is None or step == self._ckpt_step:
            return False
        state, _, _ = restore_checkpoint(self.checkpoint_dir, self._template, step)
        # killed replicas are skipped (their publish cell is frozen at
        # death); prepare_for_publish(arm=None) keeps each survivor's
        # current degrade arm across the reload
        alive = [r for r, a in zip(self.replicas, self.router.active()) if a]
        staged = [r.prepare_for_publish(state.params) for r in alive]
        with self._reload_lock:
            version = self._version + 1
            for r, prepared in zip(alive, staged):
                r.install_prepared(prepared, int(state.step), version=version,
                                   raw_params=state.params)
            self._params_host = state.params
            self._version = version
            self._ckpt_step = int(state.step)
            self.reloads += 1
        return True

    def publish_params(self, params, ckpt_step: int,
                       version: Optional[int] = None) -> None:
        """Fleet-wide publish of in-memory params — reload_now minus the
        disk restore, for callers that received new params some other way
        (the pod-loop transport ships them over the block-stream socket).
        Same lockstep discipline: stage every live replica outside the
        reload lock, install all under one shared version. `version`
        defaults to the next fleet version; an explicit value (the
        learner's broadcast version) keeps the params_version stamps on
        captured transitions comparable across hosts."""
        alive = [r for r, a in zip(self.replicas, self.router.active()) if a]
        staged = [r.prepare_for_publish(params) for r in alive]
        with self._reload_lock:
            v = self._version + 1 if version is None else int(version)
            for r, prepared in zip(alive, staged):
                r.install_prepared(prepared, int(ckpt_step), version=v,
                                   raw_params=params)
            self._params_host = params
            self._version = v
            self._ckpt_step = int(ckpt_step)
            self.reloads += 1

    def _watch_iteration(self) -> None:
        # mirrors PolicyServer._watch_iteration: bounded work per call,
        # exponential backoff on transient restore trouble
        try:
            self.reload_now()
        except (OSError, InjectedFault):
            with self._reload_lock:
                self.reload_errors += 1
            wait = self._watch_backoff.fail()
        else:
            self._watch_backoff.reset()
            wait = self.serve_cfg.poll_interval_s
        if self.supervisor is not None:
            self.supervisor.stop.wait(wait)
        else:
            time.sleep(wait)

    def _degrade_iteration(self) -> None:
        # supervised fleet-controller body: one bounded evaluation tick
        self.degrade.evaluate_once()
        if self.supervisor is not None:
            self.supervisor.stop.wait(self.degrade.cfg.eval_interval_s)
        else:
            time.sleep(self.degrade.cfg.eval_interval_s)

    # ------------------------------------------------------------ lifecycle

    def warmup(self) -> None:
        """Pre-trace every bucket on every replica (each device compiles
        its own per-bucket step)."""
        for r in self.replicas:
            r.warmup()

    def start(self, watch_checkpoints: Optional[bool] = None) -> None:
        if self.supervisor is not None:
            raise RuntimeError("server already started")
        if watch_checkpoints is None:
            watch_checkpoints = self.checkpoint_dir is not None
        for r in self.replicas:
            r.start(watch_checkpoints=False)
        self.supervisor = Supervisor()
        if watch_checkpoints:
            self.supervisor.spawn(
                "ckpt-watcher-multi",
                lambda: self._watch_iteration(),
                max_restarts=self.serve_cfg.max_restarts,
            )
        if self.degrade is not None:
            # the fleet owns the one controller (replicas spawned none:
            # their _degrade_owner was stripped in __init__)
            self.supervisor.spawn(
                "degrade-controller-multi",
                lambda: self._degrade_iteration(),
                max_restarts=self.serve_cfg.max_restarts,
            )
        if self.autoscale is not None:
            # its OWN supervised root (serve/autoscale.py): scale events
            # block on warmup/migration for whole seconds — they must
            # never share a worker with the sub-second watch/degrade ticks
            self.autoscale.start()

    def check(self) -> Dict[str, int]:
        out = {"worker_restarts": 0, "worker_stalls": 0}
        for r in self.replicas:
            c = r.check()
            out["worker_restarts"] += c.get("worker_restarts", 0)
            out["worker_stalls"] += c.get("worker_stalls", 0)
        sups = [self.supervisor]
        if self.autoscale is not None:
            sups.append(self.autoscale.supervisor)
        for sup in sups:
            if sup is not None:
                c = sup.check()
                out["worker_restarts"] += c.get("worker_restarts", 0)
                out["worker_stalls"] += c.get("worker_stalls", 0)
        return out

    def stop(self, timeout: float = 5.0) -> None:
        if self.autoscale is not None:
            # first: no scale event may fire into a stopping fleet
            self.autoscale.stop(timeout)
        if self.supervisor is not None:
            self.supervisor.shutdown(timeout)
            self.supervisor = None
        for r in self.replicas:
            r.stop(timeout)

    # ------------------------------------------------------------- metrics

    # counters summed across replicas in stats(); per-replica detail rides
    # under "replicas" for anyone who needs the breakdown
    _SUMMED = (
        "cache_sessions", "cache_evictions", "cache_admissions",
        "cache_hits", "cache_misses", "cache_readmits", "cache_spills",
        "cache_promotes", "cache_spill_evictions", "spill_sessions",
        "cache_imports", "cache_spill_sheds",
        "requests", "batches", "rejected", "shed", "deferrals",
        "queue_depth", "trace_count", "quantized_leaves", "arm_switches",
        "completed_batches", "metrics_skipped",
    )

    def stats(self) -> Dict[str, object]:
        per_replica = [r.stats() for r in self.replicas]
        out: Dict[str, object] = {
            "serve_devices": len(self.replicas),
            "ckpt_step": self._ckpt_step,
            "params_version": self._version,
            "serve_arm": self._arm,
            "reloads": self.reloads,
            "reload_errors": self.reload_errors,
            "replicas_killed": self.replicas_killed,
            "replicas_added": self.replicas_added,
            "sessions_migrated": self.sessions_migrated,
            "sessions_lost": self.sessions_lost,
            "sessions_restarted": self.sessions_restarted,
            "serve_quantization": self.cfg.serve_quantization,
        }
        # per-replica idle signals alongside the summed counters: the
        # autoscaler's drain decision reads this triplet (a replica is a
        # drain candidate when inactive traffic-wise, not merely unlucky
        # in one stats sweep)
        out["replica_active"] = self.router.active()
        out["replica_inflight"] = [
            s.get("inflight_depth", 0) for s in per_replica
        ]
        out["replica_last_request_age_s"] = [
            round(s.get("last_request_age_s", 0.0), 4) for s in per_replica
        ]
        for key in self._SUMMED:
            out[key] = sum(s.get(key, 0) for s in per_replica)
        lookups = out["cache_hits"] + out["cache_misses"]
        out["cache_hit_rate"] = out["cache_hits"] / lookups if lookups else 0.0
        # fleet-level batch shape economics from the raw batcher sums (the
        # per-replica means can't be averaged without their weights)
        batches = sum(r.batcher.batches for r in self.replicas)
        occ = sum(r.batcher.occupancy_sum for r in self.replicas)
        padded = sum(r.batcher.padded_sum for r in self.replicas)
        out["mean_batch_occupancy"] = occ / max(batches, 1)
        out["bucket_fill"] = occ / max(padded, 1)
        cache0 = self.replicas[0].cache
        out["cache_dtype"] = cache0.dtype.name
        out["session_carry_bytes"] = cache0.session_carry_bytes
        # summed per replica (not capacity * count): with a dynamic fleet
        # the killed replicas' capacity has left and added replicas' has
        # joined — only the ACTIVE replicas' rows can hold sessions
        out["cache_capacity"] = sum(
            r.cache.capacity
            for r, a in zip(self.replicas, out["replica_active"]) if a
        )
        out["spill_capacity"] = sum(
            r.cache.spill_capacity
            for r, a in zip(self.replicas, out["replica_active"]) if a
        )
        out.update(self.router.stats())
        # liveloop tap/assigner are SHARED across replicas (one instance
        # installed on all), so their stats pass through once, not summed
        for key, val in per_replica[0].items():
            if key.startswith(("eps_", "tap_")):
                out[key] = val
        if self.degrade is not None:
            out.update(self.degrade.stats())
        if self.autoscale is not None:
            out.update(self.autoscale.stats())
        out["replicas"] = per_replica
        return out
