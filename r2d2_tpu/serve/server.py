"""The serve loop: supervised batched acting with checkpoint hot-reload.

The inference-side counterpart of the training workers (SEED RL's
centralized inference, Espeholt et al. 2020): ONE thread owns the device
and the session cache, pulling micro-batches from the batcher, advancing
all sessions in a single jitted `net.act` step, and resolving each
request's Future with the chosen action. The supervised workers run under
`utils/supervision.Supervisor` exactly like the training-side actor loops:

- ``serve-loop``   — batch formation + STAGE (host assembly into the
  batcher's preallocated staging buffers, RNG draws in arrival order) +
  DISPATCH (the async jitted step and the donated in-place carry
  commit); a raising iteration fails only the in-flight batches' futures
  (recovery hook) and the loop restarts with the session cache intact;
- ``serve-complete`` — (cfg.serve_pipeline, the default) materializes
  each dispatched batch's q/action in dispatch order, resolves client
  futures, and feeds the tap, the degrade window, and metrics — so the
  serve thread stages and dispatches batch k+1 while the device still
  runs batch k. A depth-2 semaphore bounds how far staging runs ahead:
  same-session ordering and the staging buffers' double-buffer reuse
  both rely on batch k being complete before batch k+2 stages. With
  cfg.serve_pipeline=False there is no completion worker and the serve
  loop completes each batch inline — the strictly serial pre-pipeline
  path, bit-identical because both modes share one stage/dispatch body
  and the completion order is FIFO either way;
- ``ckpt-watcher`` — polls the orbax series (utils/checkpoint.py) and
  atomically publishes new params.

Hot reload is a single-attribute swap: params travel as one
``(params, ckpt_step, version, arm)`` tuple, read ONCE per batch, so every
request in a batch is answered by exactly one checkpoint — a reload
mid-traffic can never tear a batch across two param sets. In-flight
requests complete under the params they were batched with. The fourth
element is the degradation-ladder ARM ("full" | "bf16" | "int8",
serve/degrade.py): the same atomic cell that makes reloads tearless makes
arm fallback tearless — a batch runs entirely on one (params, arm) pair,
and the step function is selected per batch from the arm it read.

Bucketed shapes bound compilation: the jitted step retraces only when the
(bucket,) batch shape is new, and `trace_count` counts the retraces so
tests can pin traces <= len(buckets).
"""

from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from r2d2_tpu.config import R2D2Config
from r2d2_tpu.learner import init_train_state
from r2d2_tpu.models.core import state_spec
from r2d2_tpu.models.r2d2 import R2D2Network
from r2d2_tpu.serve.batcher import BucketStaging, MicroBatcher, ServeRequest, StagedBatch
from r2d2_tpu.serve.degrade import DegradeConfig, DegradeController
from r2d2_tpu.serve.state_cache import RecurrentStateCache
from r2d2_tpu.utils.checkpoint import latest_checkpoint_step, restore_checkpoint
from r2d2_tpu.utils.faults import Backoff, InjectedFault, fault_point, total_retries
from r2d2_tpu.utils.metrics import MetricsLogger
from r2d2_tpu.utils.profiling import span
from r2d2_tpu.utils.supervision import Supervisor


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving-plane knobs (the model/network config stays R2D2Config)."""

    buckets: Tuple[int, ...] = (2, 4, 8, 16, 32)
    max_wait_ms: float = 2.0
    queue_depth: int = 1024
    cache_capacity: int = 4096
    poll_interval_s: float = 0.5  # checkpoint watcher cadence
    epsilon: float = 0.0  # serving default: greedy
    max_restarts: int = 3
    seed: int = 0


class ServeResult:
    """One answered request: the action plus enough provenance (checkpoint
    step, params version, batch bucket) to audit which params produced it.
    `bucket` is the batch shape the request was actually served at — a
    reference replay that pads to the same bucket runs the very program
    shape the server compiled, which makes bit-parity structural instead
    of leaning on XLA's batch-size canonicalization."""

    __slots__ = ("action", "q", "ckpt_step", "params_version", "bucket")

    def __init__(self, action: int, q: np.ndarray, ckpt_step: int,
                 params_version: int, bucket: int = 0):
        self.action = action
        self.q = q
        self.ckpt_step = ckpt_step
        self.params_version = params_version
        self.bucket = bucket

    def __repr__(self) -> str:
        return (
            f"ServeResult(action={self.action}, ckpt_step={self.ckpt_step}, "
            f"params_version={self.params_version})"
        )


@dataclasses.dataclass
class _PipelineRecord:
    """One dispatched batch in flight between DISPATCH and COMPLETE.

    `q`/`action` are device arrays (futures under JAX async dispatch —
    `copy_to_host_async` was already started); `staged` pins the staging
    buffer set the batch was assembled in so the double-buffer flip
    cannot hand it back out before this record completes (the depth-2
    semaphore releases only after completion); `tap_rows` are the
    batch rows' committed carries, gathered at dispatch time on the
    serve thread so completion never touches stores a later donated
    step may already have consumed."""

    batch: List[ServeRequest]
    n: int
    bucket: int
    ckpt_step: int
    version: int
    arm: str
    q: object
    action: object
    staged: StagedBatch
    tap_rows: Optional[tuple]
    seq: int = 0  # the `batch` id of the stage and complete spans
    t_dispatched: float = 0.0  # time.monotonic(), the clock of t_enqueue


_REF_JITS: Dict[R2D2Network, object] = {}


def _pad_obs(obs: np.ndarray, target: Tuple[int, ...]) -> np.ndarray:
    """Zero-pad one request's obs up to the serving geometry (mixed-shape
    multi-task families: a smaller task's rendering rides in the top-left
    corner of the union canvas, exactly where the training-side factories
    put it when asked to render AT the union shape)."""
    target = tuple(target)
    if obs.shape == target:
        return obs
    if obs.ndim != len(target) or any(s > t for s, t in zip(obs.shape, target)):
        raise ValueError(
            f"request obs shape {obs.shape} does not fit the serve "
            f"obs_shape {target}"
        )
    return np.pad(obs, [(0, t - s) for s, t in zip(obs.shape, target)])


def reference_act(net: R2D2Network, params, obs, last_action, last_reward, carry,
                  min_batch: int = 2, task=None):
    """The direct (unbatched-service) acting path tests compare against:
    one jitted `net.act` on exactly the given sessions, padded to
    `min_batch` rows. The pad matters twice over: XLA lowers batch-1
    acting through a matrix-vector path whose reduction order differs
    bitwise from the batched matmul path, and at aggressive-enough (or
    low-enough) backend optimization levels even two matmul batch shapes
    may lower with different reduction orders. Rows are independent and
    pad-content blind at ANY level, so padding to the EXACT bucket the
    server answered at (`ServeResult.bucket`) replays the same program
    shape the server compiled and makes bit-parity structural. The
    min_batch=2 default remains the canonical standalone reference at
    XLA's default optimization level.

    `task` ((B,) int32, multi-task serving only) conditions the head the
    same way the served path does; None is the single-task golden path.

    Returns (q (B, A), (h, c)) for the B real rows.
    """
    fn = _REF_JITS.get(net)
    if fn is None:
        fn = jax.jit(
            lambda p, o, la, lr, c, t: net.apply(
                p, o, la, lr, c, task=t, method=net.act
            )
        )
        _REF_JITS[net] = fn
    obs = jnp.asarray(obs)
    la = jnp.asarray(last_action, jnp.int32)
    lr = jnp.asarray(last_reward, jnp.float32)
    if task is not None:
        task = jnp.asarray(task, jnp.int32)
    h, c = carry
    B = obs.shape[0]
    pad = max(min_batch - B, 0)
    if pad:
        obs = jnp.concatenate([obs, jnp.zeros((pad, *obs.shape[1:]), obs.dtype)])
        la = jnp.concatenate([la, jnp.zeros((pad,), jnp.int32)])
        lr = jnp.concatenate([lr, jnp.zeros((pad,), jnp.float32)])
        h = jnp.concatenate([h, jnp.zeros((pad, h.shape[1]), h.dtype)])
        c = jnp.concatenate([c, jnp.zeros((pad, c.shape[1]), c.dtype)])
        if task is not None:
            task = jnp.concatenate([task, jnp.zeros((pad,), jnp.int32)])
    q, (h_out, c_out) = fn(params, obs, la, lr, (h, c), task)
    return q[:B], (h_out[:B], c_out[:B])


class PolicyServer:
    """Session-stateful batched policy service over a trained checkpoint.

    Lifecycle: construct (params explicit, or restored from the latest
    checkpoint under `checkpoint_dir`), `start()`, submit requests (or use
    a serve.client wrapper), `stop()`. `check()` surfaces supervisor
    restart/stall counters and raises if a worker died for good — call it
    from the owning loop exactly like Trainer does.
    """

    def __init__(
        self,
        cfg: R2D2Config,
        serve_cfg: ServeConfig = ServeConfig(),
        params=None,
        checkpoint_dir: Optional[str] = None,
        metrics: Optional[MetricsLogger] = None,
        device=None,
        mesh=None,
        name: str = "",
        step_cache: Optional[Dict[bool, object]] = None,
        net=None,
        template=None,
    ):
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self.checkpoint_dir = checkpoint_dir
        self.metrics = metrics
        # replica placement (serve/multi.py): params + session rows live on
        # exactly this device; None keeps jax's default (single-device)
        self.device = device
        # sharded placement: a Mesh routes every publish — including the
        # int8-quantized tree, whose q8/scale leaves inherit the kernel
        # rules — through parallel/sharding_map.serve_param_shardings, the
        # SAME wildcard table the learner shards from. Mutually exclusive
        # with `device` (one replica is either pinned or mesh-spread).
        if mesh is not None and device is not None:
            raise ValueError("pass device= or mesh=, not both")
        self.mesh = mesh
        # worker-name suffix so multi-device supervisors tell replicas apart
        self.name = name

        # `net`/`template` (serve/multi.py passes the fleet's) skip the
        # jitted model init: the net is stateless (params are call
        # arguments) and every replica of a fleet initializes an
        # identical one from the same seed anyway — re-running init in a
        # replica forked mid-traffic would stall the serving core on the
        # init compile for nothing
        if net is not None and template is not None:
            self.net, self._template = net, template
        else:
            self.net, self._template = init_train_state(
                cfg, jax.random.PRNGKey(serve_cfg.seed)
            )
        ckpt_step = -1
        if params is None:
            if checkpoint_dir is not None and latest_checkpoint_step(checkpoint_dir) is not None:
                state, _, _ = restore_checkpoint(checkpoint_dir, self._template)
                params, ckpt_step = state.params, int(state.step)
            else:
                params = self._template.params  # fresh init (smoke serving)
        # serve_quantization="int8": per-channel symmetric weight-only
        # quantization of the encoder/head kernels (ops/quantize.py),
        # applied ONCE per publish (here and at every hot reload) so the
        # jitted step dequantizes int8 weights in-jit instead of fetching
        # f32 kernels from HBM. Default "none" publishes params as-is.
        self.quantized_leaves = 0
        # guards the mutable serve-plane state shared between the serve
        # loop, the checkpoint watcher, the fleet reload path, and
        # stop()-from-main: the publish cell + its version counter, the
        # reload counters, and the in-flight batch handoff. The slow parts
        # of a publish (quantize, device_put) stay OUTSIDE this lock —
        # only the O(1) swap happens under it (prepare_for_publish /
        # install_prepared).
        self._state_lock = threading.Lock()
        # the atomic hot-reload cell: ONE attribute holding ONE tuple, read
        # once per batch — Python attribute reads are atomic, so a batch
        # sees exactly one (params, step, version, arm), never a mix. The
        # arm rides in the same cell so a degrade-ladder fallback is as
        # tearless as a reload (indices 0-2 are unchanged for readers that
        # predate the arm, e.g. analysis/jaxpr_rules.py).
        self._published: Tuple[object, int, int, str] = (None, ckpt_step, -1, "full")
        # raw (pre-quantize, host-or-wherever) params the arms re-prepare
        # from: a bf16->int8 fallback must not re-round already-cast leaves
        self._params_raw = params
        self.arm_switches = 0
        self.publish(params, ckpt_step, version=0)

        if serve_cfg.cache_capacity < max(serve_cfg.buckets):
            # a batch's own admissions must never evict a co-batched
            # session (two rows sharing a slot): with capacity >= max
            # bucket, the LRU front is always a non-batch session
            raise ValueError(
                f"cache_capacity ({serve_cfg.cache_capacity}) must be >= the "
                f"largest batch bucket ({max(serve_cfg.buckets)})"
            )
        # carries cache at cfg.state_dtype (bf16 under precision="bf16"):
        # half the per-session HBM and gather/scatter bytes per batch.
        # cfg.serve_spill > 0 adds the host spill tier: evicted sessions
        # demote to a host-RAM slab and promote back carry-intact.
        self.cache = RecurrentStateCache(
            serve_cfg.cache_capacity, cfg.hidden_dim, dtype=cfg.state_dtype,
            spill_capacity=cfg.serve_spill, device=device,
            state_shape=state_spec(cfg)[0], core=cfg.recurrent_core,
        )
        self.batcher = MicroBatcher(
            buckets=serve_cfg.buckets,
            max_wait_s=serve_cfg.max_wait_ms / 1000.0,
            queue_depth=serve_cfg.queue_depth,
        )
        self._rng = np.random.default_rng(serve_cfg.seed)
        # preallocated per-bucket staging buffers (serve/batcher.py): batch
        # assembly writes into these instead of allocating per batch. Two
        # sets per bucket, flipped per staging — with the depth-2 pipeline
        # bound, a set is never re-staged before the batch that used it
        # fully completed.
        self._staging = BucketStaging(serve_cfg.buckets, num_tasks=cfg.num_tasks)
        # the pipeline depth bound: acquired before a batch stages,
        # released after it completes. Depth 2 = one batch on the device +
        # one staged/dispatched behind it.
        self._depth_sem = threading.Semaphore(2)
        # stage/dispatch -> complete handoff (FIFO preserves dispatch
        # order, which is completion order)
        self._complete_q: "queue.Queue[_PipelineRecord]" = queue.Queue()
        self._complete_worker = None
        self.completed_batches = 0
        # per-batch host time sums (seconds; stats() reports them, a window is
        # a delta of two stats() calls): oldest request's queue wait, stage +
        # dispatch, dispatched -> q/action on the host, then resolving the
        # futures and retiring the batch
        self._batch_ids = itertools.count(1)  # the `batch` id of a batch's spans
        self.queue_wait_s_sum = 0.0
        self.stage_s_sum = 0.0
        self.device_wait_s_sum = 0.0
        self.complete_s_sum = 0.0
        # deferred serve metrics (cfg.serve_log_interval > 0): batches that
        # skipped the metrics row, so rates stay computable from the rows
        # that did log
        self.metrics_skipped = 0
        self._metrics_last_t = float("-inf")
        self._metrics_last_arm: Optional[str] = None
        self._metrics_last_version: Optional[int] = None
        # hoisted once: per-task action dims for native exploration draws
        self._task_dims = (
            np.asarray(cfg.task_action_dims, np.int64)
            if cfg.task_action_dims else None
        )
        # live-loop capture hooks (liveloop/loop.py installs both; None —
        # the default — keeps _run_batch byte-for-byte the pre-liveloop
        # path): tap records served batches, eps_assigner maps sessions
        # to sticky exploration epsilons
        self.tap = None
        self.eps_assigner = None
        self.trace_count = 0  # python-body counter: +1 per jit trace
        self.reloads = 0
        self.reload_errors = 0
        # watcher poll escalation on transient reload failures (checkpoint
        # dir not mounted yet, step pruned between list and restore): back
        # off instead of hammering the fs at poll_interval_s
        self._watch_backoff = Backoff(
            base=serve_cfg.poll_interval_s, factor=2.0,
            max_delay=max(30.0, serve_cfg.poll_interval_s),
        )
        self._inflight: List[ServeRequest] = []
        # jitted steps by their one trace-relevant switch (in-jit dequant
        # or not); built lazily so the default config compiles exactly the
        # steps it always did. self._step tracks the last-selected one.
        # `step_cache` (serve/multi.py passes a fleet-level dict) SHARES
        # this cache across a fleet's replicas: replicas are structural
        # clones — same config, same net architecture, and every piece of
        # per-replica state (params, session stores, staging) enters the
        # step as a call argument, never closure state — so a replica the
        # autoscaler forks mid-traffic warms against the fleet's already
        # traced + compiled executables instead of stealing the serving
        # cores for a fresh trace/compile of identical programs.
        self._steps: Dict[bool, object] = (
            step_cache if step_cache is not None else {}
        )
        self._step = self._step_for(self._published[3])

        # degradation ladder (serve/degrade.py): default OFF — no
        # controller object, no admission watermark, no observe() calls,
        # the serve plane byte-for-byte as before. A fleet overrides
        # .degrade with ONE shared controller and owns its worker.
        self.degrade: Optional[DegradeController] = None
        self._degrade_owner = False
        # extra per-request latency observers (objects with .observe(s)) —
        # the autoscaler installs its own SignalWindow here when it runs
        # without a degrade ladder to share one with
        self._latency_sinks: tuple = ()
        if cfg.serve_degrade:
            self.degrade = DegradeController(
                self, DegradeConfig(slo_ms=cfg.serve_degrade_slo_ms)
            )
            self._degrade_owner = True

        self.supervisor: Optional[Supervisor] = None
        self._serve_worker = None
        self._watch_worker = None

    # ------------------------------------------------------------ jit step

    def prepare_for_publish(self, params, arm: Optional[str] = None):
        """The slow half of a publish, safe to run with NO lock held:
        the arm's weight transform (int8 quantization / weight-only bf16
        cast) plus the H2D placement onto this replica's device. Returns
        an opaque staged triple for install_prepared. The fleet reload
        path stages every replica with this before touching its reload
        lock so serving never stalls behind a device transfer.

        `arm` is the degradation-ladder rung's weight format (None keeps
        the currently published arm): "full" is the config's own behavior
        (int8 under serve_quantization="int8", verbatim otherwise);
        "bf16" casts float leaves to bfloat16 — the model's own dtype
        promotion upcasts at compute, so only weight rounding drifts;
        "int8" quantizes regardless of config."""
        if arm is None:
            arm = self._published[3]
        leaves = 0
        if arm == "int8" or (arm == "full" and self.cfg.serve_quantization == "int8"):
            from r2d2_tpu.ops.quantize import quantize_tree

            params, leaves = quantize_tree(params)
        elif arm == "bf16":
            params = jax.tree.map(
                lambda x: x.astype(jnp.bfloat16)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
                params,
            )
        elif arm != "full":
            raise ValueError(f"unknown serve arm {arm!r}")
        if self.mesh is not None:
            from r2d2_tpu.parallel.sharding_map import serve_param_shardings

            params = jax.device_put(
                params, serve_param_shardings(params, self.mesh))
        elif self.device is not None:
            params = jax.device_put(params, self.device)
        return params, leaves, arm

    def install_prepared(self, prepared, ckpt_step: int,
                         version: Optional[int] = None,
                         raw_params=None) -> None:
        """The O(1) lock-held tail of a publish: swap the publish cell
        (one tuple write) and bump the version. No device work, no I/O.
        `raw_params` (the fleet reload path) refreshes the pre-transform
        params the arms re-prepare from."""
        prepared_params, leaves, arm = prepared
        with self._state_lock:
            self.quantized_leaves = leaves
            if raw_params is not None:
                self._params_raw = raw_params
            if version is None:
                version = self._published[2] + 1
            self._published = (prepared_params, int(ckpt_step), version, arm)

    def publish(self, params, ckpt_step: int, version: Optional[int] = None,
                arm: Optional[str] = None) -> None:
        """Atomically publish a param set to this server/replica: prepare
        (the arm's weight transform), place on this replica's device —
        both outside the state lock — then swap the publish cell in ONE
        guarded write. The multi-device server stages all replicas via
        prepare_for_publish and installs with an explicit shared version
        so the fleet advances in lockstep."""
        self.install_prepared(
            self.prepare_for_publish(params, arm), ckpt_step, version,
            raw_params=params,
        )

    def set_arm(self, arm: str, params=None) -> bool:
        """Switch the degradation-ladder arm: re-prepare the RAW params
        under the new arm (outside all locks — quantize/cast + H2D) and
        swap the publish cell, preserving ckpt_step and bumping the
        version. No-op (False) when the arm is already live. Called by
        the degrade controller and the bench matrix; safe against a
        concurrent reload — whichever swap lands second wins the cell,
        and both are internally consistent (params, arm) pairs."""
        if arm == self._published[3]:
            return False
        raw = self._params_raw if params is None else params
        prepared = self.prepare_for_publish(raw, arm)
        with self._state_lock:
            ckpt_step = self._published[1]
        self.install_prepared(prepared, ckpt_step)
        with self._state_lock:
            self.arm_switches += 1
        return True

    # -------------------------------------------------- degrade surface
    # (serve/degrade.py drives these; MultiDeviceServer mirrors them)

    @property
    def queue_bound(self) -> int:
        return self.serve_cfg.queue_depth

    def queue_depth(self) -> int:
        return self.batcher.qsize()

    def set_admission(self, limit: Optional[int], budget: int = 0) -> None:
        self.batcher.set_admission(limit, budget=budget)

    def shed_spill(self, keep_fraction: float) -> int:
        return self.cache.shed_spill(keep_fraction)

    def _step_for(self, arm: str):
        """The jitted step matching an arm's published weight format.
        Only ONE switch is trace-relevant — whether the step dequantizes
        in-jit — so "full" and "bf16" share a step (bf16 leaves flow
        through the same graph at their own dtype) and the default config
        never builds more than it used to. Also updates self._step so
        external introspection (analysis/jaxpr_rules.py) always sees the
        step that last served traffic."""
        quantized = arm == "int8" or (
            arm == "full" and self.cfg.serve_quantization == "int8"
        )
        # warmup (main) and the serve loop both reach this cache; building
        # a step is cheap (jit wrapping is lazy — compilation happens at
        # the first call, outside the lock)
        with self._state_lock:
            fn = self._steps.get(quantized)
            if fn is None:
                fn = self._steps[quantized] = self._build_step(quantized)
            self._step = fn
        return fn

    def _build_step(self, quantized: bool):
        net = self.net

        def step(params, h_store, c_store, la_store, lr_store,
                 obs, rewards, slots, reset_mask, explore_mask, random_actions,
                 task=None):
            # runs once per TRACE (new bucket shape), not per call; a
            # metrics counter bumped at trace time — a lock can't live in
            # a traced function, and a lost increment under a concurrent
            # warmup/serve trace only undercounts a gauge
            self.trace_count += 1  # r2d2: disable=cross-thread-unguarded-write
            if quantized:
                # in-jit dequant: XLA fuses the i8->f32 convert + scale
                # multiply into the consuming matmuls (ops/quantize.py)
                from r2d2_tpu.ops.quantize import dequantize_tree

                params = dequantize_tree(params)
            h = h_store[slots]
            c = c_store[slots]
            la = la_store[slots]
            zero = reset_mask[:, None]
            h = jnp.where(zero, 0.0, h)
            c = jnp.where(zero, 0.0, c)
            la = jnp.where(reset_mask, 0, la)
            lr = jnp.where(reset_mask, 0.0, rewards)
            # fused act tail: dueling combine + ε-mask + argmax in one op
            # with the core step (models/r2d2.py act_select)
            q, action, (h_new, c_new) = net.apply(
                params, obs, la, lr, (h, c), explore_mask, random_actions,
                task=task, method=net.act_select,
            )
            # scatter back: pad rows all target the scratch slot (their
            # writes collide there harmlessly; real slots are unique by the
            # batcher's one-session-per-batch rule)
            # explicit downcast to the cache dtype (act may compute at a
            # wider dtype than the bf16 store holds)
            h_store = h_store.at[slots].set(h_new.astype(h_store.dtype))
            c_store = c_store.at[slots].set(c_new.astype(c_store.dtype))
            la_store = la_store.at[slots].set(action)
            lr_store = lr_store.at[slots].set(lr)
            return q, action, h_store, c_store, la_store, lr_store

        # donating the session stores lets XLA update them in place; on CPU
        # the donation is unsupported (warning noise) so it is gated off
        donate = () if jax.default_backend() == "cpu" else (1, 2, 3, 4)
        return jax.jit(step, donate_argnums=donate)

    # ------------------------------------------------------------- serving

    def submit(self, session_id: str, obs, reward: float = 0.0,
               reset: bool = False, epsilon: Optional[float] = None,
               task: int = 0) -> Future:
        return self.batcher.submit(
            session_id, obs, reward=reward, reset=reset, epsilon=epsilon,
            task=task,
        )

    def reset_session(self, session_id: str) -> None:
        self.cache.reset(session_id)

    def evict(self, session_id: str) -> None:
        """Disconnect: free the session's HBM slot and any spill row.
        Same surface as MultiDeviceServer.evict so clients (LocalClient,
        the TCP handler) work against either server unchanged."""
        self.cache.evict(session_id)
        if self.eps_assigner is not None:
            self.eps_assigner.forget(session_id)
        if self.tap is not None:
            self.tap.observe_evict(session_id)

    def _run_batch(self, batch: List[ServeRequest]) -> None:
        # the batch joins the in-flight set BEFORE any work: a crash
        # anywhere past this line reaches _serve_recover, which fails these
        # futures so no client blocks forever
        with self._state_lock:
            self._inflight = self._inflight + list(batch)
        if self.cfg.serve_pipeline and self._complete_worker is not None:
            # depth bound: at most 2 batches between stage and complete.
            # Bounded waits so a wedged completion worker cannot pin this
            # thread through a shutdown.
            while not self._depth_sem.acquire(timeout=0.25):
                if self.supervisor is not None and self.supervisor.stop.is_set():
                    raise RuntimeError("server stopping; batch not staged")
            try:
                rec = self._stage_and_dispatch(batch)
            except BaseException:
                self._depth_sem.release()
                raise
            self._complete_q.put(rec)
        else:
            # serial path (cfg.serve_pipeline=False, or a bare _run_batch
            # with no completion worker running): same stage/dispatch body,
            # completed inline — the strictly serial pre-pipeline loop
            rec = self._stage_and_dispatch(batch)
            self._complete(rec)

    def _stage_and_dispatch(self, batch: List[ServeRequest]) -> _PipelineRecord:
        """STAGE + DISPATCH, on the serve thread: assemble the batch into
        the preallocated staging buffers (RNG draws at stage time in
        arrival order — the exact stream the serial path consumes), then
        dispatch the async jitted step and commit the donated carry
        stores. Host-blocking materialization is banned here (the
        `blocking-host-sync-in-serve-step` lint enforces it); everything
        that must wait on the device lives in _complete."""
        t_stage = time.monotonic()
        queue_wait = t_stage - batch[0].t_enqueue  # the oldest request's
        seq = next(self._batch_ids)
        with span("r2d2.serve.stage", batch=seq, rows=len(batch),
                  queue_wait_us=int(queue_wait * 1e6)):
            # single read of the publish cell: the whole batch — and the
            # results' provenance — come from one (params, arm) pair; a reload
            # landing between stage and complete changes NOTHING for this
            # batch (mid-pipeline provenance invariant)
            params, ckpt_step, version, arm = self._published
            step_fn = self._step_for(arm)
            n = len(batch)
            bucket = self.batcher.bucket_for(n)
            slots, fresh = self.cache.assign([r.session_id for r in batch])

            obs_rows = [r.obs for r in batch]
            target = tuple(self.cfg.obs_shape)
            if any(o.shape != target for o in obs_rows):
                # mixed-shape task interleaving (multi-task serving): pad every
                # row to the union geometry the compiled step expects, so one
                # bucket serves the whole family without per-shape retraces
                obs_rows = [_pad_obs(o, target) for o in obs_rows]
            # zero-copy assembly: single vectorized writes into this bucket's
            # staging set (obs stack, rewards, reset|fresh, slots, task) —
            # no per-batch np.stack/np.concatenate allocs, no per-row loops
            staged = self._staging.stage(batch, bucket, obs_rows, self.serve_cfg.epsilon)
            # a row starts from zero state when the client asked for a reset OR
            # the cache admitted it fresh (new session, or evicted + returned);
            # pad rows were pre-set to reset so the scratch row never compounds
            staged.reset_mask[:n] |= fresh
            staged.slots[:n] = slots
            staged.slots[n:] = self.cache.pad_slot
            # per-row exploration: request override > per-session assignment
            # (liveloop's ladder) > the ServeConfig.epsilon fleet default.
            # RNG discipline keeps the legacy stream bit-exact: the coin and
            # random-action draws happen iff ANY row explores, in the same
            # order and count as the old scalar path — all-zero rows (the
            # default config) draw nothing, a uniform fleet epsilon draws
            # exactly what it used to. epsilon_for runs in arrival order
            # (sticky ladder rungs assign on first call).
            assigner = self.eps_assigner
            if assigner is not None:
                staged.eps[:n] = [
                    r.epsilon if r.epsilon is not None
                    else assigner.epsilon_for(r.session_id)
                    for r in batch
                ]
            elif any(r.epsilon is not None for r in batch):
                staged.eps[:n] = [
                    self.serve_cfg.epsilon if r.epsilon is None else r.epsilon
                    for r in batch
                ]
            if float(staged.eps.max()) > 0.0:
                staged.explore[:] = self._rng.random(bucket) < staged.eps
                if staged.task is not None and self._task_dims is not None:
                    # exploration stays NATIVE per row: a drawn action must be
                    # legal for the row's task, not just the union head
                    staged.randoms[:] = self._rng.integers(
                        0, self._task_dims[staged.task]
                    )
                else:
                    staged.randoms[:] = self._rng.integers(
                        0, self.cfg.action_dim, bucket
                    )

            h, c, la, lr = self.cache.arrays()
            step_args = [
                params, h, c, la, lr,
                jnp.asarray(staged.obs), jnp.asarray(staged.rewards),
                jnp.asarray(staged.slots), jnp.asarray(staged.reset_mask),
                jnp.asarray(staged.explore),
                jnp.asarray(staged.randoms, jnp.int32),
            ]
            if staged.task is not None:
                step_args.append(jnp.asarray(staged.task))
            q, action, h, c, la, lr = step_fn(*step_args)
            # JAX async dispatch: q/action come back as futures. Start the D2H
            # copy NOW so it overlaps the remaining dispatch work and the next
            # batch's staging; _complete's materialization then finds the
            # bytes already on host (or waits the residue).
            if hasattr(q, "copy_to_host_async"):
                q.copy_to_host_async()
                action.copy_to_host_async()
            # stores commit at DISPATCH time, before the next batch can stage:
            # a same-session follow-up (only admissible in a later batch)
            # gathers from these arrays, and the device stream orders the
            # donated in-place update ahead of any later step that reads it
            self.cache.commit(h, c, la, lr)
            tap_rows = None
            if self.tap is not None:
                # gather the batch rows' committed carries HERE, on the serve
                # thread: on donating backends batch k's stores are consumed
                # by step k+1, so a completion-time gather could read freed
                # buffers. The gather is itself async — dispatch-ordered after
                # the commit, materialized by the tap/completion side.
                tap_rows = self.tap.gather_rows(h, c, staged.slots[:n])
            rec = _PipelineRecord(
                batch=batch, n=n, bucket=bucket, ckpt_step=ckpt_step,
                version=version, arm=arm, q=q, action=action, staged=staged,
                tap_rows=tap_rows, seq=seq,
            )
        rec.t_dispatched = time.monotonic()
        with self._state_lock:
            self.queue_wait_s_sum += queue_wait
            self.stage_s_sum += rec.t_dispatched - t_stage
        return rec

    def _complete(self, rec: _PipelineRecord) -> None:
        """COMPLETE: materialize q/action (the only host-blocking reads in
        the serve path), resolve client futures, retire the batch from the
        in-flight set, and feed the tap, the degrade window, and metrics.
        Runs on the serve-complete worker (pipelined), or inline on the
        serve thread (serial); records arrive in dispatch order either
        way."""
        with span("r2d2.serve.complete", batch=rec.seq):
            q_np = np.asarray(rec.q)
            act_np = np.asarray(rec.action)
            t_done = time.monotonic()
            for i, r in enumerate(rec.batch):
                # .done() guard: _serve_recover may have failed these futures
                # after a serve-loop crash while this record was still queued
                if not r.future.done():
                    r.future.set_result(
                        ServeResult(int(act_np[i]), q_np[i], rec.ckpt_step,
                                    rec.version, bucket=rec.bucket)
                    )
            with self._state_lock:
                done = set(map(id, rec.batch))
                self._inflight = [r for r in self._inflight if id(r) not in done]
                self.completed_batches += 1
                self.device_wait_s_sum += t_done - rec.t_dispatched
                self.complete_s_sum += time.monotonic() - t_done
        n = rec.n
        if self.tap is not None:
            # live-loop capture, after the clients have their answers. The
            # staging buffers are REUSED (double-buffered), so the tap gets
            # copies of the buffer-backed rows — its records must survive
            # the next staging of this bucket — plus the carry rows
            # pre-gathered at dispatch time
            staged = rec.staged
            self.tap.observe_batch(
                [r.session_id for r in rec.batch],
                staged.obs[:n].copy(), act_np[:n], q_np[:n],
                staged.rewards[:n].copy(), staged.reset_mask[:n].copy(),
                staged.eps[:n].copy(), rec.ckpt_step, rec.version,
                None, None, staged.slots[:n].copy(), rows=rec.tap_rows,
            )
        if self.degrade is not None or self._latency_sinks:
            # feed the ladder's latency window and any extra sinks (per
            # answered request, the same queue-to-resolve latency clients
            # experience)
            sinks = self._latency_sinks
            for r in rec.batch:
                lat = t_done - r.t_enqueue
                if self.degrade is not None:
                    self.degrade.observe(lat)
                for s in sinks:
                    s.observe(lat)
        if self.metrics is not None:
            self._log_serve_metrics(rec, t_done)

    def _log_serve_metrics(self, rec: _PipelineRecord, t_done: float) -> None:
        """Deferred serve metrics: the full stats dict (queue probe +
        cache.stats()) is built only when a row is due —
        cfg.serve_log_interval=0.0 (default) logs every batch, the
        pre-pipeline behavior; a positive interval logs on that cadence
        plus forced rows on every arm change and reload (version bump) so
        provenance edges are never silent. Skipped batches are counted so
        rates stay computable between rows."""
        interval = self.cfg.serve_log_interval
        with self._state_lock:
            force = (
                rec.arm != self._metrics_last_arm
                or rec.version != self._metrics_last_version
            )
            due = interval <= 0.0 or (t_done - self._metrics_last_t) >= interval
            if not (due or force):
                self.metrics_skipped += 1
                return
            self._metrics_last_t = t_done
            self._metrics_last_arm = rec.arm
            self._metrics_last_version = rec.version
            completed = self.completed_batches
            skipped = self.metrics_skipped
        # the dict build (batcher/cache probes take their own locks) stays
        # OUTSIDE the state lock
        self.metrics.log(
            {
                "plane": "serve",
                "batch_occupancy": rec.n,
                "bucket": rec.bucket,
                "queue_depth": self.batcher.qsize(),
                "latency_s_oldest": t_done - rec.batch[0].t_enqueue,
                "ckpt_step": rec.ckpt_step,
                "params_version": rec.version,
                "serve_arm": rec.arm,
                "reloads": self.reloads,
                "trace_count": self.trace_count,
                "completed_batches": completed,
                "metrics_skipped": skipped,
                **self.cache.stats(),
            }
        )

    def _fail_record(self, rec: _PipelineRecord) -> None:
        """Completion-side recovery: retire a record whose completion
        raised, failing any still-unresolved futures so clients retry.
        Session state is safe — the carry committed at dispatch."""
        with self._state_lock:
            dead = set(map(id, rec.batch))
            self._inflight = [r for r in self._inflight if id(r) not in dead]
        for r in rec.batch:
            if not r.future.done():
                r.future.set_exception(
                    RuntimeError("serve completion failed; retry the request")
                )

    def _complete_iteration(self) -> None:
        """Supervised serve-complete worker body: complete one dispatched
        batch (bounded wait so shutdown never blocks). The depth slot is
        released in ALL cases — a record either completes or is failed,
        never left holding pipeline depth."""
        try:
            rec = self._complete_q.get(timeout=0.25)
        except queue.Empty:
            return
        try:
            self._complete(rec)
        except BaseException:
            self._fail_record(rec)
            raise
        finally:
            self._depth_sem.release()

    def _serve_iteration(self) -> None:
        # straggler-replica drill: a "stall:S" schedule here wedges THIS
        # replica's serve loop (queue backs up, co-replicas keep serving);
        # an "error" exercises the supervised-restart path
        fault_point("serve.replica_stall")
        batch = self.batcher.next_batch(timeout=0.25)
        if batch:
            self._run_batch(batch)

    def _degrade_iteration(self) -> None:
        """Supervised degrade-controller body: one bounded evaluation
        tick, then wait out the cadence on the stop event."""
        self.degrade.evaluate_once()
        if self.supervisor is not None:
            self.supervisor.stop.wait(self.degrade.cfg.eval_interval_s)
        else:
            time.sleep(self.degrade.cfg.eval_interval_s)

    def _serve_recover(self) -> None:
        """Restart hook: fail the in-flight batch's futures so no client
        blocks forever on a crashed iteration. The session cache needs no
        repair — stores only commit after a fully successful step, so a
        crash leaves every session at its last committed state and a
        client retry re-runs from exactly there."""
        with self._state_lock:
            inflight, self._inflight = self._inflight, []
        for r in inflight:
            if not r.future.done():
                r.future.set_exception(
                    RuntimeError("serve iteration failed; retry the request")
                )

    # ----------------------------------------------------------- hot reload

    def _watch_iteration(self) -> None:
        # bounded work per call (supervision contract): one poll, then wait
        try:
            self.reload_now()
        except (OSError, InjectedFault):
            # transient fs trouble: the step vanished between listing and
            # restore (series advanced, retention pruned it —
            # FileNotFoundError), or the checkpoint dir itself is briefly
            # unreachable (remount, NFS hiccup). Count it and re-poll with
            # exponential backoff; the next successful reload resets the
            # cadence.
            with self._state_lock:
                self.reload_errors += 1
            wait = self._watch_backoff.fail()
        else:
            self._watch_backoff.reset()
            wait = self.serve_cfg.poll_interval_s
        if self.supervisor is not None:
            self.supervisor.stop.wait(wait)
        else:
            time.sleep(wait)

    def reload_now(self) -> bool:
        """One synchronous reload check (the watcher body; also usable
        directly by tests and watcher-less servers). Returns True if new
        params were published."""
        fault_point("serve.reload")
        step = latest_checkpoint_step(self.checkpoint_dir)
        if step is None or step == self._published[1]:
            return False
        state, _, _ = restore_checkpoint(self.checkpoint_dir, self._template, step)
        self.publish(state.params, int(state.step))
        with self._state_lock:
            self.reloads += 1
        return True

    # ------------------------------------------------------------ lifecycle

    def warmup(self) -> None:
        """Pre-trace every bucket shape with pad-only batches so live
        traffic never waits on a compile. Writes touch only the scratch
        row, so session state is untouched. The staging buffers warm
        alongside the compiles: a replica the autoscaler adds mid-traffic
        enters the rotation with no first-batch allocations left to pay.

        With a degrade ladder attached, the quality arms' executables
        warm too — bf16 is a new dtype signature, int8 a new (in-jit
        dequant) step — because an arm switch fires UNDER overload by
        definition: a switch that stalls the serving core on a fresh
        trace+compile mid-crest is a worse latency cliff than the
        pressure it answers. The trace budget is then arms x buckets
        (analysis/jaxpr_rules.check_trace_budget's `arms`); the warm
        params are staged copies, dropped after warmup — the publish
        cell never moves."""
        self._staging.warm(self.cfg.obs_shape, np.uint8)
        params, _, _, arm = self._published
        warm_arms = [(arm, params)]
        if self.degrade is not None:
            for rung_arm in ("bf16", "int8"):
                if rung_arm != arm:
                    p, _, _ = self.prepare_for_publish(
                        self._params_raw, rung_arm
                    )
                    warm_arms.append((rung_arm, p))
        for warm_arm, warm_params in warm_arms:
            step_fn = self._step_for(warm_arm)
            for bucket in self.batcher.buckets:
                obs = np.zeros((bucket, *self.cfg.obs_shape), np.uint8)
                h, c, la, lr = self.cache.arrays()
                warm_args = [
                    warm_params, h, c, la, lr,
                    jnp.asarray(obs), jnp.zeros(bucket, jnp.float32),
                    jnp.full(bucket, self.cache.pad_slot, jnp.int32),
                    jnp.ones(bucket, bool), jnp.zeros(bucket, bool),
                    jnp.zeros(bucket, jnp.int32),
                ]
                if self.cfg.num_tasks > 1:
                    warm_args.append(jnp.zeros(bucket, jnp.int32))
                out = step_fn(*warm_args)
                q, action, h, c, la, lr = out
                jax.block_until_ready(q)
                # commit: on donating backends the old stores were consumed
                self.cache.commit(h, c, la, lr)
        # leave the published arm as the last-selected step (analysis
        # introspection reads self._step)
        self._step_for(arm)

    def start(self, watch_checkpoints: Optional[bool] = None) -> None:
        if self.supervisor is not None:
            raise RuntimeError("server already started")
        if watch_checkpoints is None:
            watch_checkpoints = self.checkpoint_dir is not None
        self.supervisor = Supervisor()
        # lambda indirection so tests can monkeypatch _serve_iteration and
        # exercise the restart path on the live worker
        suffix = f"-{self.name}" if self.name else ""
        if self.cfg.serve_pipeline:
            # spawned BEFORE the serve loop so the first batch already
            # sees a completion worker and takes the pipelined path
            self._complete_worker = self.supervisor.spawn(
                "serve-complete" + suffix,
                lambda: self._complete_iteration(),
                max_restarts=self.serve_cfg.max_restarts,
            )
        self._serve_worker = self.supervisor.spawn(
            "serve-loop" + suffix,
            lambda: self._serve_iteration(),
            max_restarts=self.serve_cfg.max_restarts,
            on_restart=self._serve_recover,
        )
        if watch_checkpoints:
            self._watch_worker = self.supervisor.spawn(
                "ckpt-watcher" + suffix,
                lambda: self._watch_iteration(),
                max_restarts=self.serve_cfg.max_restarts,
            )
        if self.degrade is not None and self._degrade_owner:
            # only the controller's OWNER spawns its worker: fleet
            # replicas share the fleet's controller and must not run
            # N competing evaluation loops against it
            self.supervisor.spawn(
                "degrade-controller" + suffix,
                lambda: self._degrade_iteration(),
                max_restarts=self.serve_cfg.max_restarts,
            )

    def check(self) -> Dict[str, int]:
        """Supervisor passthrough: restart/stall counters for the metrics
        stream; raises WorkerFatalError when a worker is out of restarts."""
        if self.supervisor is None:
            return {"worker_restarts": 0, "worker_stalls": 0}
        return self.supervisor.check()

    def stop(self, timeout: float = 5.0) -> None:
        if self.supervisor is not None:
            self.supervisor.shutdown(timeout)
            self.supervisor = None
        self._complete_worker = None
        # drain the pipeline: records the completion worker never reached
        # are completed inline — their steps already dispatched, so their
        # clients still deserve answers (falling back to _fail_record only
        # if completion itself raises)
        while True:
            try:
                rec = self._complete_q.get_nowait()
            except queue.Empty:
                break
            try:
                self._complete(rec)
            except Exception:
                self._fail_record(rec)
            finally:
                self._depth_sem.release()
        for r in self.batcher.drain():
            if not r.future.done():
                r.future.set_exception(RuntimeError("server stopped"))
        self._serve_recover()  # anything mid-batch when the loop stopped

    def stats(self) -> Dict[str, object]:
        out: Dict[str, object] = {
            "reloads": self.reloads,
            "reload_errors": self.reload_errors,
            "io_retries": total_retries(),
            "trace_count": self.trace_count,
            "ckpt_step": self._published[1],
            "params_version": self._published[2],
            "serve_arm": self._published[3],
            "arm_switches": self.arm_switches,
            "serve_quantization": self.cfg.serve_quantization,
            "quantized_leaves": self.quantized_leaves,
            "completed_batches": self.completed_batches,
            "queue_wait_s_sum": self.queue_wait_s_sum,
            "stage_s_sum": self.stage_s_sum,
            "device_wait_s_sum": self.device_wait_s_sum,
            "complete_s_sum": self.complete_s_sum,
            "metrics_skipped": self.metrics_skipped,
            # dispatched-not-yet-completed requests: with the queue depth
            # and last_request_age_s (batcher stats) this is the idle
            # signal triplet the autoscaler's drain decision reads
            "inflight_depth": len(self._inflight),
        }
        out.update(self.batcher.stats())
        out.update(self.cache.stats())
        if self.eps_assigner is not None:
            out.update(self.eps_assigner.stats())
        if self.tap is not None:
            out.update(self.tap.stats())
        if self.degrade is not None and self._degrade_owner:
            out.update(self.degrade.stats())
        return out
