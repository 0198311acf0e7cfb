"""Frozen dataclass configuration (L0).

The reference keeps hyperparameters as mutable module globals imported at
definition time (reference config.py:1-37, with values bound inside default
args — SURVEY.md quirk notes). Here config is a frozen dataclass constructed
once and passed explicitly, so values are visible to jit as static Python
scalars and configs can be swapped per-experiment without import-order traps.

All default values reproduce the reference exactly
(/root/reference/config.py:1-37).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

# The recurrent cores: name -> "module:Class". The ONE table that names a
# core: validation reads its keys, models/core.py resolves the class (a
# dotted path, so that validating a config imports no flax), and everything
# else asks that class. Adding a core is one line here plus its module
# (ARCHITECTURE.md, "Adding a recurrent core").
RECURRENT_CORES = {
    "lstm": "r2d2_tpu.models.lstm:LSTM",
    "lru": "r2d2_tpu.models.lru:LRU",
    "hybrid_stack": "r2d2_tpu.models.hybrid_stack:HybridStack",
}


@dataclasses.dataclass(frozen=True)
class R2D2Config:
    # --- environment -----------------------------------------------------
    env_name: str = "MsPacman"
    # TPU-native layout is channels-last (NHWC): conv input tiles onto the
    # MXU without a transpose. The reference uses channel-first (1, 84, 84)
    # (reference config.py:2); env wrappers here emit (84, 84, 1).
    obs_shape: Tuple[int, ...] = (84, 84, 1)
    action_dim: int = 9  # MsPacman reduced action set; overridden per env
    max_episode_steps: int = 27000  # reference config.py:17
    noop_max: int = 30  # reference environment.py:9

    # --- optimization ----------------------------------------------------
    lr: float = 1e-4  # reference config.py:4
    # lr schedule over training_steps (the reference trains at constant
    # lr, config.py:4). "cosine" decays to lr*lr_final_frac by
    # training_steps and holds there — the round-3 long-context runs
    # (LSTM and LRU both) climbed clearly above chance then REGRESSED
    # under constant lr; the decayed tail is the designed stabilizer.
    # The schedule reads the optimizer's own update count, so it
    # survives checkpoint resume at the right position.
    lr_schedule: str = "constant"  # constant | cosine
    lr_final_frac: float = 0.1
    adam_eps: float = 1e-3  # reference config.py:5
    grad_norm: float = 40.0  # reference config.py:6
    batch_size: int = 64  # reference config.py:7

    # --- RL --------------------------------------------------------------
    gamma: float = 0.997  # reference config.py:11
    value_rescale_eps: float = 1e-3  # reference worker.py:455

    # --- multi-task plane (multitask/, ROADMAP item 2) -------------------
    # num_tasks = 1 keeps every golden path bit-exact: no task field in
    # replay, no task input to the network, no head widening. > 1 turns on
    # the task-conditioned dueling head (one-hot task embedding into the
    # heads), the per-block task stamp through replay, and the task pass
    # through the train step — Agent57-style one-learner-many-tasks
    # (Badia et al. 2020) over the pure-JAX env family.
    num_tasks: int = 1
    # env name per task id (the registry order); empty outside multi-task
    multitask_envs: Tuple[str, ...] = ()
    # native action count per task. action_dim is the UNION width; tasks
    # with fewer actions get their invalid tail masked out of the dueling
    # head (argmax and bootstrap max can never pick them). Empty = every
    # task uses the full union.
    task_action_dims: Tuple[int, ...] = ()
    # per-task discount ladder (Agent57's gamma ladder). Empty = cfg.gamma
    # for every task. Discounts travel through the STORED per-step gamma
    # field, so only collection reads this — the learner is unchanged.
    task_gammas: Tuple[float, ...] = ()

    # --- prioritized replay ----------------------------------------------
    prio_exponent: float = 0.9  # alpha, reference config.py:12
    is_exponent: float = 0.6  # beta, reference config.py:13
    # per-sequence priority = eta*max|td| + (1-eta)*mean|td|
    # (reference worker.py:325; paper's eta = 0.9)
    td_mix_eta: float = 0.9
    buffer_capacity: int = 2_000_000  # transitions, reference config.py:16
    block_length: int = 400  # reference config.py:19
    learning_starts: int = 50_000  # reference config.py:8

    # --- sequence shape --------------------------------------------------
    burn_in_steps: int = 40  # reference config.py:27
    learning_steps: int = 40  # reference config.py:28
    forward_steps: int = 5  # n-step, reference config.py:29
    # ABLATION knob (R2D2 paper section 3's zero-state baseline): replayed
    # sequences start from ZERO recurrent state instead of the stored one.
    # Pair with burn_in_steps=0 to reproduce the paper's zero-state
    # training strategy; the memory_catch learning runs use it to prove
    # the stored-state + burn-in machinery is load-bearing. Acting is
    # unaffected (the actor always carries true episode state).
    zero_state_replay: bool = False

    # --- schedule / cadences (reference worker.py:440-452, config.py:9-15)
    training_steps: int = 100_000
    target_net_update_interval: int = 2000
    save_interval: int = 500
    # learner publishes weights to actors every N updates (worker.py:440)
    publish_interval: int = 4
    # actors refresh weights every N env steps. The reference hardcodes 400
    # at worker.py:744 and never reads config.actor_update_interval
    # (SURVEY.md quirk 4); here it is honored.
    actor_update_interval: int = 400
    log_interval: float = 10.0  # seconds, reference config.py:24

    # --- actor fleet ------------------------------------------------------
    num_actors: int = 8  # reference config.py:21
    # host env pools: > 0 steps the E envs across a persistent thread pool
    # of this size (ThreadedHostEnvPool — emulators release the GIL, so a
    # many-core host parallelizes them; the reference used 8 processes).
    # 0 = serial loop. Ignored by the pure-JAX vec envs (already batched).
    env_pool_workers: int = 0
    # collection pacing (threaded mode): target ratio of learner-consumed
    # transitions to collected transitions (the Acme/Reverb
    # samples-per-insert knob). 0 = free-running actors (the reference's
    # behavior). When the observed ratio falls below the target — data is
    # plentiful relative to optimization — the actor thread yields,
    # leaving the device to the learner; above it, collection resumes.
    samples_per_insert: float = 0.0
    base_eps: float = 0.4  # reference config.py:22
    eps_alpha: float = 7.0  # reference config.py:23
    test_epsilon: float = 0.001  # reference config.py:37

    # --- network ----------------------------------------------------------
    hidden_dim: int = 512  # reference config.py:34
    encoder: str = "nature"  # "nature" | "impala" | "mlp"
    # width multiplier for the impala encoder's channel stack
    impala_channels: Tuple[int, ...] = (16, 32, 32)

    # --- numerics ---------------------------------------------------------
    # Compute dtype for conv/LSTM matmuls. Loss/target math always runs in
    # float32 (SURVEY.md section 7.3 item 4). bfloat16 feeds the MXU at
    # double rate on TPU.
    compute_dtype: str = "float32"  # "float32" | "bfloat16"
    param_dtype: str = "float32"
    # Master mixed-precision policy. "fp32" keeps the golden path bit-exact:
    # compute follows the compute_dtype knob above and recurrent-state
    # STORAGE stays float32 everywhere. "bf16" switches the whole compute
    # plane to bfloat16 (overriding compute_dtype — see
    # resolved_compute_dtype) AND stores LSTM/LRU carries in bfloat16
    # across every replay plane, replay snapshots, and the serve state
    # cache: half the hidden-state HBM footprint and H2D staging bytes.
    # Params + optimizer state stay float32 master copies regardless
    # (the model casts on use), as do the fp32 correctness islands:
    # Q-head/dueling math, value rescale, n-step target folding, TD
    # error / priorities, IS weighting, and the loss reduction
    # (learner.py loss_fn, models/r2d2.py _dueling).
    precision: str = "fp32"  # "fp32" | "bf16"

    # Serve-plane weight quantization (serve/server.py). "none" serves the
    # checkpoint params as-is (bit-exact golden path). "int8" quantizes the
    # encoder/head matmul kernels to per-output-channel symmetric int8 at
    # publish time (checkpoint hot-reload / initial publish) and
    # dequantizes in-jit inside the serve step: weights ship to the device
    # at a quarter (vs fp32) of the bytes and the jitted step carries an
    # i8 -> compute-dtype convert instead of an HBM-resident f32 kernel.
    # The recurrent core (wi/wh/b) and all biases stay full precision —
    # the sequential carry is the drift amplifier, so only the wide
    # feed-forward matmuls take the quantization error. Bounded-parity
    # class, like precision="bf16": actions may differ from the fp32 arm
    # only where Q-gaps are within the quantization error (tests pin the
    # Q-value drift bound); NOT bit-exact. Train/learner paths never see
    # this knob. Default off.
    serve_quantization: str = "none"  # "none" | "int8"

    # Serve-plane session spill tier (serve/state_cache.py). The HBM
    # session cache is fixed-capacity; without a spill tier an LRU-evicted
    # session restarts from zero carry when it returns — exactly the
    # burn-in state the R2D2 policy needs (the paper's stored-state
    # argument applies to serving too). serve_spill > 0 preallocates a
    # host-RAM slab of that many sessions (np.zeros is lazy on Linux, so
    # a multi-million-session slab costs physical pages only as it
    # fills): eviction DEMOTES (h, c, last_action, last_reward) into the
    # slab, a returning session PROMOTES it back bit-exactly (dtype
    # preserved, fp32 and bf16 alike), and only never-seen (or
    # spill-evicted) sessions start fresh. Addressable sessions become
    # host-memory-bound instead of HBM-bound. 0 keeps PR-2 semantics:
    # evicted sessions readmit fresh.
    serve_spill: int = 0
    # Serve-plane replication (serve/multi.py). > 1 runs one full serve
    # stack (session cache + micro-batcher + supervised serve loop) per
    # local device with session-affinity routing in front: a session's
    # carry lives on exactly ONE device, new sessions hash to the
    # least-loaded replica, and checkpoint hot-reload publishes to all
    # replicas in one pass (int8 re-quantization included). Each replica
    # keeps the compile-once-per-bucket property independently.
    serve_devices: int = 1
    # Serve-plane graceful-degradation ladder (serve/degrade.py). When
    # True the server runs a supervised "degrade-controller" worker that
    # watches queue depth, windowed p99 latency, and SLO attainment
    # against serve_degrade_slo_ms, and steps a rung ladder with
    # hysteresis: full -> admission control at the micro-batcher (bounded
    # QueueFullError shed) -> weight-only bf16 arm -> int8 arm + spill
    # slab pressure shed. Every rung transition is stamped into stats.
    # Default False: NO controller exists, no admission watermark is
    # installed, and the publish path is byte-for-byte the pre-ladder
    # behavior — the golden serve paths stay bit-exact.
    serve_degrade: bool = False
    # The ladder's SLO target: p99 above this (or attainment below the
    # controller's low-water band) counts as a pressured evaluation.
    serve_degrade_slo_ms: float = 50.0
    # Elastic autoscaler (serve/autoscale.py). When True the fleet runs a
    # supervised "autoscaler" control loop that watches the same sliding-
    # window signals the degrade ladder does (queue fraction, windowed
    # p99, SLO attainment against serve_degrade_slo_ms) and scales the
    # REPLICA SET instead of the quality ladder: sustained pressure for
    # autoscale_dwell_up ticks spawns a warmed replica on a free device
    # (MultiDeviceServer.add_replica — published under the fleet's shared
    # params version, then routed), sustained health for
    # autoscale_dwell_down ticks drains the least-loaded replica through
    # the kill_replica migration path (sessions spill-migrate, zero loss).
    # The degrade ladder stays the millisecond shock absorber: while a
    # scale-up is pending/landing the ladder may step down quality; in
    # steady state quality steps are gated off so capacity — not quality
    # — answers sustained pressure. Default False: NO autoscaler object
    # or thread exists and the fleet is byte-for-byte the static-size
    # behavior (the golden serve/scenario rows stay bit-exact).
    serve_autoscale: bool = False
    # Fleet size bounds the autoscaler may move between. serve_devices is
    # the STARTING size; min/max clamp every scale decision.
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 2
    # Consecutive pressured/healthy evaluation ticks before a scale event
    # (the autoscaler's hysteresis dwell, same contract as the ladder's).
    autoscale_dwell_up: int = 2
    autoscale_dwell_down: int = 12
    # Seconds after any scale event during which no further event fires
    # (replica warmup + router rebalance settle inside the cooldown).
    autoscale_cooldown_s: float = 2.0
    # Evaluation tick interval for the autoscaler worker, in seconds.
    autoscale_interval_s: float = 0.25
    # Scale-up pressure judges windowed p99 against THIS FRACTION of the
    # SLO budget (serve_degrade_slo_ms), not the full budget: a replica
    # takes seconds to warm, so capacity must be bought while latency
    # still has headroom, not after misses start. Healthy/recovery
    # verdicts (and the degrade ladder) still judge the full SLO.
    autoscale_pressure_margin: float = 0.8
    # A drain candidate must have gone this long without a request (its
    # last_request_age_s idle signal) OR be the fleet's least-loaded
    # replica while the whole fleet is healthy.
    autoscale_idle_age_s: float = 1.0
    # When True (default) a scale-down HOLDS until some replica is truly
    # idle (zero in-flight work, no request for autoscale_idle_age_s):
    # the fleet's health signals describe the fleet at its CURRENT size
    # and are blind to what the smaller fleet would feel, so a
    # comfortable fleet at a traffic crest must not drain a replica into
    # the crest and pay the migration wave at peak. False: the healthy
    # dwell alone decides and the least-loaded replica drains.
    autoscale_drain_requires_idle: bool = True
    # Depth-2 serve pipeline (serve/server.py). When True (default) each
    # batch is split into STAGE (host assembly into preallocated
    # per-bucket staging buffers, RNG draws in arrival order, then the
    # async jitted step dispatch + donated in-place carry commit) and
    # COMPLETE (a supervised per-replica "serve-complete" worker
    # materializes q/action in dispatch order, resolves client futures,
    # and feeds the tap, the degrade window, and metrics) — so the serve
    # thread stages and dispatches batch k+1 while the device still runs
    # batch k. Bounded to depth 2 so cache assign/commit bookkeeping and
    # same-session ordering stay correct; RNG draws happen at stage time
    # in arrival order, so served actions are BITWISE identical to the
    # serial path. False restores the strictly serial pre-pipeline loop
    # (one thread stages, steps, and resolves), bit-identically.
    serve_pipeline: bool = True
    # Serve metrics cadence in seconds: the per-batch serve metrics dict
    # (which includes a full cache.stats() sweep) is logged at most this
    # often, plus forced logs on arm or params-version changes so
    # reload/degrade events are never invisible. Batches skipped between
    # logs are counted (metrics_skipped rides in the logged dict) so
    # rates stay computable. 0.0 logs every batch — the pre-pipeline
    # behavior.
    serve_log_interval: float = 0.0

    # Live-loop learning plane (liveloop/). When True the serve plane
    # grows a TransitionTap: every served step's (obs, action, reward,
    # carry-seam, epsilon, params_version) is captured off the hot path
    # into per-session SequenceAccumulators, finished Blocks drain
    # through a bounded ingestion bridge into the configured replay
    # plane, and a LiveLoopTrainer trains continuously against the live
    # store — checkpoints land where the serve watcher hot-reloads them,
    # closing serve -> replay -> learn -> publish into one
    # self-improving service. Default False: NO tap is installed, no
    # liveloop threads exist, and the serve/train paths are byte-for-
    # byte the pre-liveloop behavior (the golden rows stay bit-exact).
    liveloop: bool = False
    # Fraction of admitted sessions assigned an exploring epsilon from
    # the Ape-X ladder (ops/epsilon.py over base_eps/eps_alpha) at
    # session admission; the rest serve greedy (eps = 0). The assigned
    # epsilon is stamped into every captured transition for off-policy
    # audit and surfaced in stats().
    liveloop_explore_fraction: float = 0.5
    # Rungs of the per-session exploration ladder (epsilon_ladder's
    # num_actors argument): rung i gets base_eps ** (1 + i/(N-1)*alpha).
    liveloop_eps_rungs: int = 8
    # Bounded depths for the two liveloop hand-off queues, in items.
    # Both shed drop-oldest (counted in stats) under pressure so the
    # serve loop is never blocked by the learner: tap depth is batch
    # records awaiting accumulation, queue depth is finished Blocks
    # awaiting replay ingestion.
    liveloop_tap_depth: int = 256
    liveloop_queue_depth: int = 64

    # Pod-loop block-stream transport (transport/): the process-boundary
    # analog of the in-process liveloop bridge. A serve host plugs a
    # BlockStreamPublisher in as the bridge's replay sink; the learner
    # runs an IngestService that fans N host streams into its replay
    # plane. None of these knobs change any behavior unless the transport
    # endpoints are actually constructed (the podloop CLI, tests) — the
    # single-process golden paths never read them.
    #
    # Publisher spool bound, in blocks: finished Blocks awaiting
    # acknowledgement (including the whole disconnected window) are kept
    # in a bounded at-least-once spool; when full the OLDEST unacked
    # block is shed and counted (fresh experience beats stale, same
    # policy as the liveloop bridge queue).
    transport_spool_depth: int = 512
    # Directory for the publisher's on-disk spool ("" = in-memory only).
    # With a directory, every spooled block is persisted as
    # <host>/<seq>.blk before it is eligible to send, and a restarted
    # publisher (SIGKILL drill) reloads the unacked tail and resumes its
    # sequence numbering from disk.
    transport_spool_dir: str = ""
    # Publisher heartbeat cadence in seconds (idle connections still
    # prove liveness) and the learner-side dead-peer timeout after which
    # a silent host connection is reaped. The timeout must exceed the
    # cadence with real headroom or healthy-but-quiet hosts flap.
    transport_heartbeat_s: float = 1.0
    transport_dead_peer_s: float = 10.0
    # Socket connect/handshake timeout for one attempt (the reconnect
    # loop wraps attempts in jittered backoff on top of this).
    transport_connect_timeout_s: float = 5.0

    # Replay disk tier (replay/disk_tier.py): memory-mapped fixed-geometry
    # segment files below the host slab in the tiered plane. Default off
    # (capacity 0) keeps every existing plane byte-identical — no segment
    # file is ever opened, the control plane keeps its host-only tree, and
    # the pointer-window staleness mask is untouched. With a capacity, the
    # host slab never evicts on wrap: the sum-tree plane picks the
    # LOWEST-priority resident block as the demotion victim and spills it
    # to a segment record; its leaves stay live in the (extended) tree so
    # demoted sequences remain sampleable — the staging thread pages them
    # in through the mmap, hidden behind the H2D double buffer. True
    # eviction only happens when the disk tier itself wraps.
    #
    # Capacity is in transitions (like buffer_capacity) and must be a
    # multiple of block_length; the tier requires replay_plane="tiered"
    # (the only plane with an off-critical-path staging thread to decode
    # on) and a non-empty directory.
    replay_disk_dir: str = ""
    replay_disk_capacity: int = 0
    # Block codec (replay/codec.py): "none" (default — wire frames, spool
    # entries, and segment records all byte-compatible with pre-codec
    # binaries) or "delta-zlib" (delta-along-time + deflate on the uint8
    # obs field; every other field rides raw). Applies to disk segment
    # records, the publisher's on-disk spool, and BLOCK wire frames — the
    # wire half is negotiated per connection over HELLO, so a new
    # publisher facing an old ingest service transparently falls back to
    # raw frames (and vice versa).
    block_codec: str = "none"

    # --- parallelism ------------------------------------------------------
    # Data-parallel learner shards the batch over the "dp" mesh axis;
    # "tp" shards wide layers (impala encoder / LSTM kernels) when > 1.
    dp_size: int = 1
    tp_size: int = 1
    # fsdp axis size (parallel/sharding_map.py): > 1 adds a third mesh axis
    # that shards the optimizer-state mu/nu trees (the next-largest HBM
    # residents after backward residuals) over their first divisible dim.
    # Params stay replicated over fsdp (ZeRO-1 style): grads are computed
    # from whole params, only the Adam moments live sharded. CLI: --fsdp.
    # Under partitioning="manual" the axis is promoted to ZeRO-2: the
    # batch ALSO shards over fsdp and gradients reduce-scatter onto the
    # moment shards (learner.make_manual_train_step).
    fsdp_size: int = 1
    # Train-step partitioning strategy on a device mesh:
    #   "gspmd"  — plain jit (or dp-manual shard_map planes): the XLA
    #              SPMD partitioner propagates the param shardings. The
    #              historical path; miscompiles the recurrent scan when
    #              tp-sharded params meet a 3-axis mesh (PR 14).
    #   "manual" — the whole train step runs inside ONE shard_map that is
    #              manual over EVERY mesh axis, with per-leaf
    #              PartitionSpecs from the sharding_map table
    #              (learner.make_manual_train_step): tp splits the
    #              LSTM/head kernels with explicit all-gather/psum seams
    #              at the gate matmuls, the batch shards over dp x fsdp,
    #              and gradients reduce-scatter over fsdp (ZeRO-2). The
    #              SPMD partitioner never sees the scan, which is what
    #              makes tp x fsdp compose.
    #   "auto"   — "manual" exactly on the tp>1 x fsdp>1 cell (where
    #              GSPMD cannot go), else "gspmd" (every existing plane
    #              keeps its bit-exact program).
    partitioning: str = "auto"
    # Named model-size presets (config.MODEL_PRESETS): "base" keeps the
    # run preset's own dims; "wide"/"xl" grow hidden_dim, "deep"/
    # "deep_wide" stack encoder_depth extra latent layers. Applied as
    # plain field overrides by apply_model_preset() (train.py
    # --model-preset).
    model_preset: str = "base"
    # Extra Dense(latent)+relu layers appended to the encoder trunk after
    # the (possibly tp-sharded) latent projection — the deeper-encoder
    # dial (models/encoders.py). The extra layers are replicated under
    # tp (no new sharding rules). 0 = the historical trunks, bit-exact.
    encoder_depth: int = 0
    # chunk size for remat'd long-sequence scans. SCAN-BACKEND KNOB ONLY:
    # the Pallas unroll stores no per-gate residuals (gates are recomputed
    # in its backward kernel), so it has nothing to remat — when the pallas
    # backend is active, scan_chunk is intentionally unused and the config
    # stays valid for the CPU/scan fallback the test suite runs.
    scan_chunk: Optional[int] = None
    # LSTM unroll backend: "auto" = fused Pallas kernel on TPU, lax.scan
    # elsewhere; "scan"/"pallas" force one (ops/pallas_lstm.py)
    lstm_backend: str = "auto"
    # recurrent core family, a name of RECURRENT_CORES above: "lstm"
    # (reference parity, sequential unroll) or "lru" (models/lru.py —
    # diagonal linear recurrence whose unroll is ONE associative_scan:
    # O(log T) depth over time, the long-context core). What a core stores
    # in replay, and whether it cuts the gradient at burn-in, is the core's
    # own statement (models/core.py).
    recurrent_core: str = "lstm"
    # lru only: > 0 switches the unroll from one associative scan
    # (bandwidth-bound: ~log2 T full sweeps over four f32 (B,T,H)
    # arrays) to per-chunk causal triangular matmuls on the MXU with a
    # T/chunk carry scan — same math, different summation order
    # (models/lru.py LRU.chunk). 0 keeps the scan.
    lru_chunk: int = 0
    # lru only: eigenvalue ring |lambda| ~ U(r_min, r_max) at init — the
    # memory-horizon dial (time constant ~ 1/(1-r)). The 0.9/0.999
    # default holds ~10..1000-step memories; push r_min/r_max toward 1
    # (e.g. 0.98/0.9999) when the task's blind span exceeds ~1000 steps
    # or when probing whether a plateau is a forgetting problem
    # (models/lru.py _ring_init).
    lru_r_min: float = 0.9
    lru_r_max: float = 0.999
    # hybrid_stack only: the layer pattern and the layers' widths under the
    # names their published config gives them (models/hybrid_stack.py
    # `StackSpec` lists and checks the keys; a core without such keys leaves
    # it empty). Given as a dict (a benchmark configuration file's JSON) or
    # as pairs; held as sorted pairs, so the config stays hashable.
    core_config: Tuple = ()

    # --- infra ------------------------------------------------------------
    seed: int = 0
    # supervision (utils/supervision.py): restart budget per worker thread
    # and seconds of silent heartbeat before a stall is reported; a stall
    # beyond stall_fatal_timeout fails the run loudly (a wedged thread
    # cannot be recovered in-process — restart with --resume; 0 disables)
    worker_max_restarts: int = 3
    heartbeat_timeout: float = 120.0
    stall_fatal_timeout: float = 900.0
    checkpoint_dir: str = "checkpoints"
    # persist replay contents (replay/snapshot.py) at end of run and
    # restore them on --resume: a resumed run continues from the SAME
    # replay distribution instead of refilling from scratch. Costs one
    # obs-store-sized .npz write (~7 KB/transition at 84x84).
    snapshot_replay: bool = False
    # > 0: also write the replay snapshot every N learner updates, off the
    # hot path (background thread; the previous snapshot is kept until the
    # new one lands via atomic rename). Requires snapshot_replay=True. A
    # crash between checkpoints then restarts from a recent replay
    # distribution instead of the run's start.
    snapshot_every: int = 0
    # on --resume, a replay snapshot whose embedded topology manifest does
    # not match the current (dp, tp, process_count) layout is regathered
    # to logical block order and re-dealt across the new layout
    # (replay/reshard.py) instead of aborting with TopologyMismatch. Same
    # logical shard set => bit-exact resume; dp change => deterministic
    # re-deal (bounded drift). CLI: --reshard.
    reshard_on_resume: bool = False
    # tiered plane only: stage chunks synchronously on the consumer thread
    # instead of the prefetch pipeline. Removes the staging-thread RNG race
    # with priority write-backs, making the tiered sampling stream
    # bit-reproducible (the chaos suite's resume-exactness contract);
    # costs the pipeline's overlap, so keep False for throughput runs.
    deterministic_staging: bool = False
    metrics_path: Optional[str] = None  # jsonl metrics file
    use_native_replay: bool = True  # C++ replay core if built, else numpy
    # replay data plane: "host" (numpy store, batches shipped per update),
    # "tiered" (full-capacity host store + double-buffered HBM staging
    # pipeline hiding the host->HBM copies behind the K-update scan;
    # replay/tiered_store.py), "device" (HBM store + fused in-jit gather,
    # single chip), "sharded" (HBM store sharded over the dp mesh axis +
    # shard_map train step), "multihost" (per-process local shards over a
    # GLOBAL mesh — the jax.distributed scale-out of "sharded";
    # replay/multihost_store.py)
    replay_plane: str = "host"
    # experience collection: "host" (VectorizedActor — batched jitted
    # policy, env stepped on host) or "device" (collect.DeviceCollector —
    # the WHOLE loop incl. env dynamics and block packing in one jitted
    # scan; needs a pure-JAX functional env and replay_plane="device")
    collector: str = "host"
    # learner updates folded into one dispatch (device plane only):
    # lax.scan over K pre-drawn coordinate sets amortizes the per-call
    # launch latency K-fold (learner.make_fused_multi_train_step). K > 1
    # trades priority/publish granularity for throughput — the reference's
    # own pipeline already lags ~12 batches (worker.py:364-371).
    updates_per_dispatch: int = 1
    # where the prioritized sum tree lives: "host" (numpy/C++ f64 tree,
    # stratified draws + priority write-backs on the host thread — today's
    # bit-exact behavior on every plane) or "device" (float32 JAX-array
    # tree in HBM, replay/device_sum_tree.py: sampling, IS weights, and
    # priority write-back all happen inside the learner dispatch, so the
    # K-update scan is no longer fenced by host tree work on either side).
    # "device" rides the device/sharded replay planes only.
    priority_plane: str = "host"
    # priority_plane="device" only: N fused K-update dispatches chained in
    # ONE lax.scan (megastep.make_priority_superstep) — the host re-enters
    # the loop every N*K updates for ingestion/metrics/snapshots. Within a
    # superstep, later dispatches sample from the tree updated by earlier
    # ones (no one-dispatch priority lag) and do not see blocks ingested
    # mid-flight; both are the documented superstep semantics
    # (ARCHITECTURE.md priority plane section). 1 = plain per-dispatch
    # device sampling.
    superstep_dispatches: int = 1

    # --- derived ----------------------------------------------------------
    @property
    def resolved_compute_dtype(self) -> str:
        """Effective matmul/activation dtype for the model cores.

        precision="bf16" forces bfloat16 compute; precision="fp32" defers
        to the legacy compute_dtype knob, so pre-policy presets (bf16
        matmuls + f32 state) keep their exact behavior and goldens."""
        return "bfloat16" if self.precision == "bf16" else self.compute_dtype

    @property
    def state_dtype(self):
        """Numpy dtype for STORED recurrent carries — the single source of
        truth read by every replay plane's hidden store
        (replay/block.store_field_specs, ReplayBuffer.hidden_store,
        DeviceReplayBuffer.pad_block_fields), the device collector's block
        packing, and the serve RecurrentStateCache. bfloat16 is numpy-side
        ml_dtypes.bfloat16 (a jax dependency), so host slabs, npz
        snapshots, and device stores all agree on the byte layout."""
        import numpy as np  # deferred: config stays import-light

        if self.precision == "bf16":
            import ml_dtypes

            return np.dtype(ml_dtypes.bfloat16)
        return np.dtype(np.float32)

    @property
    def tp_shards_params(self) -> bool:
        """True when tp>1 actually shards the LSTM kernels via GSPMD (the
        rule lives here ONCE: config validation, the model's LSTM backend
        resolution, and the Trainer's state placement all read it).

        Plain-jit planes: GSPMD partitions from the param shardings alone.
        The "sharded" shard_map plane composes the same way — its maps are
        manual over dp ONLY (axis_names={"dp"}), leaving tp GSPMD-auto, so
        tp-sharded params partition the per-dp-shard update body (learner.
        make_sharded_fused_*). Only the multihost plane pins tp=1.

        Under resolved_partitioning="manual" the params are STILL
        tp-sharded (same table, same placement) — only the partitioner
        changes — so every caller's placement/backend decision holds."""
        return self.tp_size > 1 and self.replay_plane != "multihost"

    @property
    def resolved_partitioning(self) -> str:
        """"manual" or "gspmd" — the effective train-step partitioning.
        "auto" resolves to manual exactly on the tp x fsdp cell GSPMD
        miscompiles; everywhere else the historical paths keep their
        bit-exact programs."""
        if self.partitioning != "auto":
            return self.partitioning
        return "manual" if (self.tp_size > 1 and self.fsdp_size > 1) else "gspmd"

    @property
    def resolved_core_backend(self) -> str:
        """"pallas" | "scan" | "lru": the recurrent-core implementation
        this config runs on the process's jax backend. THE resolution of
        lstm_backend="auto" — models/r2d2.from_config builds the net from
        it and the entry-point banner (utils/runtime.py) prints it, so the
        choice is never made
        silently at trace time. auto = the fused Pallas kernel on a TPU,
        lax.scan elsewhere, and scan wherever the update body sits under
        a GSPMD-partitioned mesh axis: Mosaic refuses a pallas_call there
        ("Mosaic kernels cannot be automatically partitioned"). On more
        than one device that leaves the kernel to the bodies that are
        fully manual — the sharded/multihost shard_map planes with every
        non-dp axis of size 1 (parallel/mesh.dp_manual_axes) and the
        manual-partitioned step; plain-jit planes over a dp mesh and
        anything tp-sharded run the scan core."""
        if self.recurrent_core != "lstm":
            return self.recurrent_core
        if self.lstm_backend != "auto":
            return self.lstm_backend
        return "pallas" if self._mosaic_call_allowed() else "scan"

    def _mosaic_call_allowed(self) -> bool:
        """Whether the update body may hold a Mosaic (Pallas TPU) call: a TPU
        backend, and no GSPMD-partitioned mesh axis over the body
        (resolved_core_backend states the rule; the LSTM's and the LRU's
        kernels both sit under it)."""
        if self.tp_shards_params:
            return False
        if self.dp_size * self.tp_size * self.fsdp_size > 1 and not (
            self.resolved_partitioning == "manual"
            or (
                self.replay_plane in ("sharded", "multihost")
                and self.tp_size == 1
                and self.fsdp_size == 1
            )
        ):
            return False
        import jax  # deferred: config stays import-light

        return jax.default_backend() == "tpu"

    def _rows_per_device(self) -> int:
        """Rows of a training batch that one device holds: the batch shards
        over dp (and over fsdp too under manual partitioning's ZeRO-2 data
        layout)."""
        shards = max(self.dp_size, 1)
        if self.resolved_partitioning == "manual":
            shards *= max(self.fsdp_size, 1)
        return max(self.batch_size // shards, 1)

    @property
    def resolved_lru_recurrence(self) -> str:
        """"pallas" | "scan" | "chunked": how the LRU core's training unroll
        runs its recurrence (models/lru.py), resolved as the LSTM's backend
        is, from what the code observes. lru_chunk > 0 selects the chunked
        MXU form; otherwise the sequential Pallas kernel (ops/pallas_lru.py)
        wherever a Mosaic call may sit (resolved_core_backend's rule) and the
        training batch's rows per device and H are whole (8, 128) tiles, and
        jax.lax.associative_scan everywhere else (CPU, GSPMD meshes, odd
        shapes). The module repeats the shape test on what it is called
        with, so an unroll at another batch size falls back by itself."""
        if self.lru_chunk > 0:
            return "chunked"
        from r2d2_tpu.ops.pallas_lru import kernel_fits  # deferred, as above

        fits = kernel_fits(self._rows_per_device(), self.hidden_dim)
        return "pallas" if fits and self._mosaic_call_allowed() else "scan"

    @property
    def resolved_frame_block(self) -> int:
        """The block `s` in which the encoder's first conv reads a frame
        (models/encoders.frame_block: the Nature trunk's stride 4 where it
        divides the frame's height and width, else 1). The device stores
        keep each frame's bytes in that order (replay/block.frames_to_rows)
        and the step programs hand the encoder frames as stored; 1 is
        frames as they are, everywhere."""
        from r2d2_tpu.models.encoders import frame_block  # deferred, as above

        return frame_block(self.encoder, self.obs_shape)

    @property
    def seq_len(self) -> int:
        """burn_in + learning + forward = 85 at defaults (config.py:30)."""
        return self.burn_in_steps + self.learning_steps + self.forward_steps

    @property
    def seqs_per_block(self) -> int:
        """Sequences per block: 400 // 40 = 10 (reference worker.py:79)."""
        return self.block_length // self.learning_steps

    @property
    def num_blocks(self) -> int:
        """Circular store size: capacity // block_length (worker.py:78)."""
        return self.buffer_capacity // self.block_length

    @property
    def num_sequences(self) -> int:
        """Priority-tree leaf count: capacity // learning (worker.py:76)."""
        return self.buffer_capacity // self.learning_steps

    @property
    def block_slot_len(self) -> int:
        """Max stored steps per block incl. leading burn-in context and the
        trailing +1 seed entry (reference Block obs shape, worker.py:26-27
        with the carry at worker.py:640-647)."""
        return self.block_length + self.burn_in_steps + 1

    def _validate_env_geometry(self, env_name: str, obs_shape) -> None:
        """Episode-cap/obs-shape sanity for every name-parameterized
        functional family (catch, keydoor, drift, banditgrid). Unknown
        names (atari, scripted, procmaze — the latter validates in its own
        geometry builder) pass through."""
        from r2d2_tpu.envs.catch import catch_params, is_catch_name

        if is_catch_name(env_name):
            p = catch_params(env_name)
            need = (
                (obs_shape[0] - 2)
                * p.get("fall_every", 1)
                * p.get("balls", 1)
            )
            if self.max_episode_steps < need:
                raise ValueError(
                    f"max_episode_steps={self.max_episode_steps} truncates "
                    f"{env_name!r} at obs {obs_shape} before the "
                    f"last ball lands (needs >= {need}): every episode "
                    "would end reward-free"
                )
            return
        from r2d2_tpu.envs.banditgrid import banditgrid_params, is_banditgrid_name
        from r2d2_tpu.envs.drift import drift_params, is_drift_name
        from r2d2_tpu.envs.keydoor import keydoor_params, is_keydoor_name

        if is_keydoor_name(env_name):
            p = keydoor_params(env_name)
            if self.max_episode_steps < p["length"]:
                raise ValueError(
                    f"max_episode_steps={self.max_episode_steps} ends "
                    f"{env_name!r} before the door (corridor length "
                    f"{p['length']}) is reachable: every episode would "
                    "end reward-free"
                )
            if obs_shape[0] < 3 or obs_shape[1] < max(p["length"], p["num_colors"]):
                raise ValueError(
                    f"obs {obs_shape} cannot render {env_name!r}: needs "
                    f"height >= 3 and width >= "
                    f"{max(p['length'], p['num_colors'])} (corridor + cue row)"
                )
        elif is_drift_name(env_name):
            drift_params(env_name)  # value errors on bad :EVERY suffixes
            if obs_shape[0] < 2 or obs_shape[1] < 3:
                raise ValueError(
                    f"obs {obs_shape} cannot render {env_name!r}: needs "
                    "height >= 2 (target + agent rows) and width >= 3"
                )
        elif is_banditgrid_name(env_name):
            p = banditgrid_params(env_name)
            if obs_shape[0] < p["grid"] or obs_shape[1] < p["grid"]:
                raise ValueError(
                    f"obs {obs_shape} cannot render {env_name!r}: the "
                    f"{p['grid']}x{p['grid']} arm grid needs height and "
                    "width >= grid"
                )
            if self.max_episode_steps < 2:
                raise ValueError(
                    f"max_episode_steps={self.max_episode_steps} gives "
                    f"{env_name!r} no post-move payout step"
                )

    def validate(self) -> "R2D2Config":
        if self.block_length % self.learning_steps != 0:
            raise ValueError("block_length must be a multiple of learning_steps")
        if self.buffer_capacity % self.block_length != 0:
            raise ValueError("buffer_capacity must be a multiple of block_length")
        if self.forward_steps < 1:
            raise ValueError("forward_steps must be >= 1")
        if self.action_dim > 256:
            # actions are stored uint8 in the replay plane (Block.action)
            raise ValueError("action_dim > 256 would overflow uint8 replay storage")
        if self.encoder not in ("nature", "impala", "mlp"):
            raise ValueError(f"unknown encoder {self.encoder!r}")
        if self.precision not in ("fp32", "bf16"):
            raise ValueError(
                f"unknown precision {self.precision!r}; 'fp32' keeps the "
                "bit-exact golden path, 'bf16' enables the mixed-precision "
                "compute plane + half-width carry storage"
            )
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"unknown compute_dtype {self.compute_dtype!r}")
        if self.serve_quantization not in ("none", "int8"):
            raise ValueError(
                f"unknown serve_quantization {self.serve_quantization!r}; "
                "'none' serves checkpoint params as-is, 'int8' enables "
                "publish-time per-channel weight quantization on the serve "
                "plane (ops/quantize.py)"
            )
        if self.serve_spill < 0:
            raise ValueError(
                "serve_spill is the host-RAM session spill capacity in "
                "sessions; it must be >= 0 (0 disables the spill tier)"
            )
        if self.serve_devices < 1:
            raise ValueError(
                "serve_devices must be >= 1 (replicas of the serve stack "
                "over local devices, serve/multi.py)"
            )
        if self.serve_degrade_slo_ms <= 0.0:
            raise ValueError(
                "serve_degrade_slo_ms is the degradation ladder's p99 "
                "latency target in milliseconds (serve/degrade.py); it "
                "must be > 0"
            )
        if self.serve_log_interval < 0.0:
            raise ValueError(
                "serve_log_interval is the serve metrics cadence in "
                "seconds (0.0 logs every batch); it must be >= 0"
            )
        if self.autoscale_min_replicas < 1:
            raise ValueError(
                "autoscale_min_replicas must be >= 1 (the autoscaler may "
                "never drain the last replica, serve/autoscale.py)"
            )
        if self.autoscale_max_replicas < self.autoscale_min_replicas:
            raise ValueError(
                "autoscale_max_replicas must be >= autoscale_min_replicas "
                "(the fleet-size band the autoscaler moves inside)"
            )
        if self.autoscale_dwell_up < 1 or self.autoscale_dwell_down < 1:
            raise ValueError(
                "autoscale_dwell_up/autoscale_dwell_down are consecutive-"
                "tick hysteresis dwells; both must be >= 1"
            )
        if self.autoscale_cooldown_s < 0.0:
            raise ValueError(
                "autoscale_cooldown_s is the post-scale-event quiet period "
                "in seconds; it must be >= 0"
            )
        if self.autoscale_interval_s <= 0.0:
            raise ValueError(
                "autoscale_interval_s is the autoscaler's evaluation tick "
                "interval in seconds; it must be > 0"
            )
        if self.autoscale_idle_age_s < 0.0:
            raise ValueError(
                "autoscale_idle_age_s is the drain candidate's idle "
                "threshold in seconds; it must be >= 0"
            )
        if not 0.0 < self.autoscale_pressure_margin <= 1.0:
            raise ValueError(
                "autoscale_pressure_margin is the fraction of the SLO "
                "budget at which scale-up pressure triggers; it must be "
                "in (0, 1]"
            )
        if self.serve_autoscale and not (
            self.autoscale_min_replicas
            <= self.serve_devices
            <= self.autoscale_max_replicas
        ):
            raise ValueError(
                "serve_autoscale requires the starting fleet size "
                f"(serve_devices={self.serve_devices}) to sit inside "
                f"[autoscale_min_replicas={self.autoscale_min_replicas}, "
                f"autoscale_max_replicas={self.autoscale_max_replicas}]"
            )
        if not 0.0 <= self.liveloop_explore_fraction <= 1.0:
            raise ValueError(
                "liveloop_explore_fraction is the share of live sessions "
                "assigned an exploring epsilon from the ladder; it must "
                "be in [0, 1]"
            )
        if self.liveloop_eps_rungs < 1:
            raise ValueError(
                "liveloop_eps_rungs must be >= 1 (rungs of the per-"
                "session exploration ladder, ops/epsilon.py)"
            )
        if self.liveloop_tap_depth < 1 or self.liveloop_queue_depth < 1:
            raise ValueError(
                "liveloop_tap_depth and liveloop_queue_depth are bounded "
                "hand-off queue depths; both must be >= 1"
            )
        if self.transport_spool_depth < 1:
            raise ValueError(
                "transport_spool_depth bounds the publisher's unacked "
                "block spool; it must be >= 1"
            )
        if self.transport_heartbeat_s <= 0.0 or \
                self.transport_connect_timeout_s <= 0.0:
            raise ValueError(
                "transport_heartbeat_s and transport_connect_timeout_s "
                "must be > 0"
            )
        if self.transport_dead_peer_s <= self.transport_heartbeat_s:
            raise ValueError(
                "transport_dead_peer_s is the ingest service's silence "
                "threshold for reaping a host connection; it must exceed "
                "transport_heartbeat_s (with headroom) or healthy idle "
                "hosts flap"
            )
        if self.block_codec not in ("none", "delta-zlib"):
            raise ValueError(f"unknown block_codec {self.block_codec!r}")
        if self.replay_disk_capacity < 0:
            raise ValueError("replay_disk_capacity must be >= 0")
        if self.replay_disk_capacity > 0:
            if not self.replay_disk_dir:
                raise ValueError(
                    "replay_disk_capacity needs replay_disk_dir: the disk "
                    "tier's segment files must live somewhere"
                )
            if self.replay_disk_capacity % self.block_length != 0:
                raise ValueError(
                    "replay_disk_capacity must be a multiple of "
                    "block_length (the disk tier holds whole blocks)"
                )
            if self.replay_plane != "tiered":
                raise ValueError(
                    "the replay disk tier hangs below the tiered plane's "
                    "host slab (its staging thread is where demoted rows "
                    "are paged in + decoded); set replay_plane='tiered' "
                    "or replay_disk_capacity=0"
                )
        if self.lstm_backend not in ("auto", "scan", "pallas"):
            raise ValueError(f"unknown lstm_backend {self.lstm_backend!r}")
        if self.recurrent_core not in RECURRENT_CORES:
            raise ValueError(
                f"unknown recurrent_core {self.recurrent_core!r}; registered: "
                f"{sorted(RECURRENT_CORES)}"
            )
        if bool(self.core_config) != (self.recurrent_core == "hybrid_stack"):
            raise ValueError(
                "core_config holds the hybrid_stack core's pattern and widths "
                "(models/hybrid_stack.py): that core needs it and no other "
                f"takes it; recurrent_core={self.recurrent_core!r} with "
                f"{len(self.core_config)} keys"
            )
        if self.lru_chunk < 0:
            raise ValueError("lru_chunk must be >= 0")
        if self.lru_chunk > 0 and self.recurrent_core != "lru":
            raise ValueError(
                "lru_chunk is the LRU core's unroll formulation; set "
                "recurrent_core='lru' (or leave lru_chunk=0)"
            )
        if not 0.0 < self.lru_r_min <= self.lru_r_max < 1.0:
            raise ValueError(
                "lru eigenvalue ring needs 0 < lru_r_min <= lru_r_max < 1 "
                f"(|lambda| < 1 is the stability guarantee), got "
                f"[{self.lru_r_min}, {self.lru_r_max}]"
            )
        if self.lr_schedule not in ("constant", "cosine"):
            raise ValueError(f"unknown lr_schedule {self.lr_schedule!r}")
        if not 0.0 <= self.lr_final_frac <= 1.0:
            raise ValueError("lr_final_frac must be in [0, 1]")
        if self.recurrent_core == "lru" and self.lstm_backend == "pallas":
            raise ValueError(
                "lstm_backend='pallas' is the fused LSTM kernel; the lru "
                "core has no pallas backend (its associative_scan unroll "
                "is already time-parallel) — use lstm_backend='auto'"
            )
        if self.tp_shards_params and self.lstm_backend == "pallas":
            raise ValueError(
                "tp_size > 1 shards the LSTM kernels via GSPMD, which "
                "cannot partition the Pallas unroll; use "
                "lstm_backend='scan' (or 'auto', which resolves to scan "
                "there)"
            )
        if self.fsdp_size < 1:
            raise ValueError("fsdp_size must be >= 1")
        if self.fsdp_size > 1 and self.replay_plane == "multihost":
            raise ValueError(
                "replay_plane='multihost' keeps params/opt-state replicated "
                "per its P() in_specs; fsdp_size > 1 is a single-controller "
                "mesh feature (parallel/sharding_map.py)"
            )
        if self.partitioning not in ("auto", "gspmd", "manual"):
            raise ValueError(
                f"unknown partitioning {self.partitioning!r}; 'gspmd' is "
                "the historical XLA-SPMD path, 'manual' the explicitly "
                "shard_mapped train step, 'auto' picks manual exactly on "
                "the tp x fsdp cell"
            )
        if self.fsdp_size > 1 and self.tp_size > 1:
            # the tp x fsdp cell: supported ONLY by the manual-partition
            # step — under GSPMD it stays precisely blocked
            if self.resolved_partitioning != "manual":
                raise ValueError(
                    "partitioning='gspmd' composes fsdp with dp only: "
                    "tp-sharded params on a 3-axis mesh miscompile the "
                    "recurrent scan under the XLA SPMD partitioner (the "
                    "forward's values change — caught by tests/"
                    "test_sharding_map.py's equivalence probe). Use "
                    "partitioning='manual' (or leave it 'auto'), which "
                    "takes the partitioner out of the loop by running the "
                    "step in one explicitly-partitioned shard_map"
                )
        if self.resolved_partitioning == "manual":
            if self.replay_plane != "host":
                raise ValueError(
                    "partitioning='manual' is the host-batch train step "
                    "(learner.make_manual_train_step); the device/sharded/"
                    "tiered/multihost planes keep their own shard_map or "
                    "GSPMD programs — use replay_plane='host'"
                )
            if self.tp_size > 1 and self.hidden_dim % self.tp_size != 0:
                raise ValueError(
                    f"manual tp splits the latent/gate/head kernels into "
                    f"contiguous column slices; hidden_dim={self.hidden_dim} "
                    f"must divide by tp_size={self.tp_size}"
                )
            shards = max(self.dp_size, 1) * max(self.fsdp_size, 1)
            if self.batch_size % shards != 0:
                raise ValueError(
                    f"partitioning='manual' shards the batch over dp x fsdp "
                    f"(ZeRO-2 data layout); batch_size={self.batch_size} "
                    f"must divide by dp_size*fsdp_size={shards}"
                )
        if self.encoder_depth < 0:
            raise ValueError("encoder_depth must be >= 0 (extra latent layers)")
        if self.model_preset not in MODEL_PRESETS:
            raise ValueError(
                f"unknown model_preset {self.model_preset!r}; one of "
                f"{sorted(MODEL_PRESETS)} (config.MODEL_PRESETS)"
            )
        # Functional-family geometry guards: an episode cap shorter than
        # the env's first possible reward means NO signal ever fires —
        # training proceeds silently on zeros (found via the long_context
        # obs_shape re-target, round 5, for catch; the same silent failure
        # class exists for every name-parameterized family, so each gets
        # its own episode-cap/obs-shape sanity check here instead of
        # silently skipping validation). Deferred import: the env modules
        # pull jax; config stays import-light until first validate.
        if self.env_name:
            self._validate_env_geometry(self.env_name, self.obs_shape)
        for i, task_env in enumerate(self.multitask_envs):
            # per-task envs render into the union obs canvas, so each must
            # pass the same geometry checks against the shared obs_shape
            try:
                self._validate_env_geometry(task_env, self.obs_shape)
            except ValueError as e:
                raise ValueError(f"multitask_envs[{i}]: {e}") from e
        if self.num_tasks < 1:
            raise ValueError("num_tasks must be >= 1")
        if self.multitask_envs and len(self.multitask_envs) != self.num_tasks:
            raise ValueError(
                f"multitask_envs names {len(self.multitask_envs)} envs for "
                f"num_tasks={self.num_tasks}; one env name per task id"
            )
        if self.task_action_dims:
            if len(self.task_action_dims) != self.num_tasks:
                raise ValueError(
                    f"task_action_dims has {len(self.task_action_dims)} "
                    f"entries for num_tasks={self.num_tasks}"
                )
            for i, a in enumerate(self.task_action_dims):
                if not 1 <= a <= self.action_dim:
                    raise ValueError(
                        f"task_action_dims[{i}]={a} outside [1, action_dim="
                        f"{self.action_dim}] — action_dim is the union width"
                    )
        if self.task_gammas:
            if len(self.task_gammas) != self.num_tasks:
                raise ValueError(
                    f"task_gammas has {len(self.task_gammas)} entries for "
                    f"num_tasks={self.num_tasks}"
                )
            for i, g in enumerate(self.task_gammas):
                if not 0.0 < g < 1.0:
                    raise ValueError(f"task_gammas[{i}]={g} outside (0, 1)")
        if self.replay_plane not in (
            "host", "tiered", "device", "sharded", "multihost"
        ):
            raise ValueError(f"unknown replay_plane {self.replay_plane!r}")
        if self.snapshot_every < 0:
            raise ValueError("snapshot_every must be >= 0")
        if self.snapshot_every > 0 and not self.snapshot_replay:
            raise ValueError(
                "snapshot_every > 0 schedules periodic replay snapshots; "
                "it requires snapshot_replay=True"
            )
        if self.deterministic_staging and self.replay_plane != "tiered":
            raise ValueError(
                "deterministic_staging is the tiered plane's synchronous "
                "staging mode; set replay_plane='tiered' (or leave it False)"
            )
        if self.replay_plane == "multihost":
            if self.tp_size != 1:
                raise ValueError("replay_plane='multihost' supports tp_size=1")
        if self.collector not in ("host", "device"):
            raise ValueError(f"unknown collector {self.collector!r}")
        if self.updates_per_dispatch < 1:
            raise ValueError("updates_per_dispatch must be >= 1")
        if self.updates_per_dispatch > 1 and self.replay_plane not in (
            "tiered", "device", "sharded", "multihost"
        ):
            raise ValueError(
                "updates_per_dispatch > 1 is implemented for the tiered, "
                "device, sharded, and multihost replay planes (fused in-jit "
                "gathers / staged K-batch chunks)"
            )
        if self.training_steps % self.updates_per_dispatch != 0:
            raise ValueError(
                "training_steps must be a multiple of updates_per_dispatch "
                "(each dispatch advances the step counter by that amount)"
            )
        if self.priority_plane not in ("host", "device"):
            raise ValueError(f"unknown priority_plane {self.priority_plane!r}")
        if self.priority_plane == "device" and self.replay_plane not in (
            "device", "sharded"
        ):
            raise ValueError(
                "priority_plane='device' keeps the sum tree in HBM next to "
                "the store; it requires replay_plane='device' or 'sharded'"
            )
        if self.superstep_dispatches < 1:
            raise ValueError("superstep_dispatches must be >= 1")
        if self.superstep_dispatches > 1 and self.priority_plane != "device":
            raise ValueError(
                "superstep_dispatches > 1 chains N fused dispatches with "
                "in-jit sampling/write-back between them; it requires "
                "priority_plane='device'"
            )
        if (
            self.training_steps
            % (self.updates_per_dispatch * self.superstep_dispatches)
            != 0
        ):
            raise ValueError(
                "training_steps must be a multiple of updates_per_dispatch "
                "* superstep_dispatches (each superstep advances the step "
                "counter by that amount)"
            )
        if self.collector == "device" and self.replay_plane in ("host", "tiered"):
            raise ValueError(
                "collector='device' writes packed blocks straight into the "
                "HBM store; it requires replay_plane='device', 'sharded', "
                "or 'multihost'"
            )
        if self.replay_plane == "sharded":
            if self.dp_size * self.tp_size <= 1:
                raise ValueError("replay_plane='sharded' needs a device mesh "
                                 "(dp_size * tp_size > 1)")
            if self.num_blocks % max(self.dp_size, 1) != 0:
                raise ValueError("num_blocks must divide evenly over dp_size")
            if self.batch_size % max(self.dp_size, 1) != 0:
                raise ValueError("batch_size must divide evenly over dp_size")
        return self

    def __post_init__(self):
        pairs = self.core_config
        if isinstance(pairs, dict):
            pairs = pairs.items()
        def frozen(v):
            # a published group of keys (a dict) as sorted pairs, a list as a tuple
            if isinstance(v, dict):
                return tuple(sorted((str(k), frozen(w)) for k, w in v.items()))
            return tuple(frozen(w) for w in v) if isinstance(v, list) else v

        object.__setattr__(
            self, "core_config", tuple(sorted((str(k), frozen(v)) for k, v in pairs))
        )

    def replace(self, **kw) -> "R2D2Config":
        return dataclasses.replace(self, **kw).validate()


# --------------------------------------------------------------------------
# Presets — the BASELINE.json configs as first-class presets.
# --------------------------------------------------------------------------

def default_atari(game: str = "MsPacman") -> R2D2Config:
    """Reference HYPERPARAMETERS: single learner, 8 actors (BASELINE.json
    config 1). Numerics intentionally diverge (see PARITY.md):

    compute_dtype is bfloat16, NOT the reference's float32: conv/LSTM
    matmuls feed the MXU at double rate while loss/target math stays f32
    (models/r2d2.py head-math contract; pinned by tests/test_model.py and
    the bf16-vs-f32 learning parity of the bench suite). Override with
    --set compute_dtype=float32 to reproduce reference numerics bit-class."""
    return R2D2Config(env_name=game, compute_dtype="bfloat16").validate()


def atari_v4_8(game: str = "MsPacman") -> R2D2Config:
    """256 actors + data-parallel learner on a v4-8 (BASELINE.json config 2)."""
    return R2D2Config(
        env_name=game,
        num_actors=256,
        dp_size=4,
        batch_size=64,
        compute_dtype="bfloat16",
        # full reference capacity fits in HBM once sharded 4-way
        replay_plane="sharded",
    ).validate()


def procgen_impala(game: str = "procmaze") -> R2D2Config:
    """IMPALA-ResNet encoder variant (BASELINE.json config 4). The default
    env is the pure-JAX procedurally-generated maze (envs/procmaze.py) —
    per-episode layout keys reproduce procgen's level-diversity property
    on-device; pass an ALE/procgen name to point at an emulator env
    instead where one is installed."""
    # geometry knobs are procmaze-specific; an emulator game keeps the
    # generic defaults (action_dim auto-corrects from the env at Trainer
    # construction, max_episode_steps stays the Atari-style cap)
    from r2d2_tpu.envs.procmaze import is_procmaze_name

    kw = dict(action_dim=5, max_episode_steps=96) if is_procmaze_name(game) else {}
    return R2D2Config(
        env_name=game,
        obs_shape=(64, 64, 3),
        encoder="impala",
        compute_dtype="bfloat16",
        **kw,
    ).validate()


def long_context(
    game: str = "memory_catch:10:8:4",
    obs_shape: tuple = (26, 26, 1),
) -> R2D2Config:
    """seq_len=581 stored-state burn-in stretch config (BASELINE.json
    config 5). The LSTM recurrence is sequential in time, so long sequences
    scale via remat-chunked lax.scan over time (SURVEY.md section 5.7), not
    sequence-dimension sharding.

    The default task (re-targeted in round 5, VERDICT r4 item 4) is the
    MULTI-BALL slow-fall flashing-cue catch (envs/catch.py,
    memory_catch:10:8:4): 768-step episodes of four balls, each with its
    own 10-step cue and ~170-step blind fall — inside the measured
    temporal frontier (runs/temporal_frontier.jpg: solves <= 216 blind
    steps) — spanning TWO 512-step learning windows per block.
    Demonstrated positive at the preset's own shape: stored-state 3.06
    vs measured null -1.91 (ceiling +4, runs/long_context_mb/). The
    zero-state control ALSO reaches 3.0 (noisier: 2.06-3.0 vs 2.88-3.06
    over the final checkpoints, runs/long_context_mb_zs/) — the
    within-window balls teach a cue-memory circuit that generalizes
    across the window boundary at eval, the R2D2 paper's own
    observation about when zero-state replay suffices; the load-bearing
    demonstrations for the stored-state machinery stand at the
    single-ball rungs (runs/long_context_mid6* pair). Net defaults
    below are the demonstrated recipe (26x26 IMPALA, hidden 128, LRU
    core, cosine lr).

    The round-4 default, memory_catch:8:12 at 84x84 (blind ~880), sits
    far BEYOND that frontier — it trains stably but no arm has separated
    from its null (runs/long_context_attacks.jpg); pass it explicitly —
    long_context("memory_catch:8:12", obs_shape=(84, 84, 4)) — to work
    the open problem (episode geometry follows obs_shape, so the cap
    comes out right: 82 rows x fall-12 = 984). Pass any other env name
    to retarget (e.g. a NetHack/Craftax-class env where one is
    installed) and override the net defaults per env; the catch-specific
    geometry below applies only to catch-family names. The benchmark's
    lru-seq581 configuration pins its own shapes (benchmark/configs/), so
    this default does not move its workload."""
    from r2d2_tpu.envs.catch import catch_params, is_catch_name

    kw = {}
    if is_catch_name(game):
        p = catch_params(game)
        fall = p.get("fall_every", 1)
        balls = p.get("balls", 1)
        # per ball: (rows-2) fall rows x fall steps/row; balls land in turn
        kw = dict(
            action_dim=3,
            max_episode_steps=(obs_shape[0] - 2) * fall * balls,
        )
    return R2D2Config(
        env_name=game,
        obs_shape=obs_shape,
        encoder="impala",
        impala_channels=(8, 16),
        hidden_dim=128,
        recurrent_core="lru",
        lr_schedule="cosine",
        burn_in_steps=64,
        learning_steps=512,
        forward_steps=5,
        block_length=1024,  # 2 learning windows per block
        buffer_capacity=2_048_000,  # 2000 blocks of 1024
        scan_chunk=64,
        compute_dtype="bfloat16",
        **kw,
    ).validate()


def tiny_test() -> R2D2Config:
    """Minimal shapes for fast unit/integration tests."""
    return R2D2Config(
        obs_shape=(12, 12, 1),
        action_dim=4,
        hidden_dim=32,
        batch_size=8,
        burn_in_steps=4,
        learning_steps=4,
        forward_steps=2,
        block_length=16,
        buffer_capacity=640,
        learning_starts=64,
        num_actors=2,
        training_steps=50,
        target_net_update_interval=10,
        save_interval=25,
        max_episode_steps=100,
        encoder="mlp",
        # 0.0 = emit every record: tests assert per-update metrics streams
        # (learning curves, record counts); the deferred-fetch throttle is
        # a production-cadence concern (Trainer._log)
        log_interval=0.0,
    ).validate()


PRESETS = {
    "atari": default_atari,
    "atari_v4_8": atari_v4_8,
    "procgen_impala": procgen_impala,
    "long_context": long_context,
    "tiny_test": tiny_test,
}


# --------------------------------------------------------------------------
# Model-size presets — the "grow the brain" dials (ISSUE 16). Orthogonal to
# the run PRESETS above: a run preset fixes the task/replay geometry, a
# model preset scales the net within it. Values are plain field overrides
# (apply_model_preset), so the resulting config is fully explicit.
MODEL_PRESETS = {
    # historical dims of whatever run preset is active
    "base": {},
    # wider LSTM/latent: 4x the core matmul FLOPs/bytes of hidden 512 —
    # the first rung that NEEDS tp on 16 GB chips at batch 64
    "wide": {"hidden_dim": 1024},
    # 2048-wide core: ~16x base core size; tp x fsdp territory
    "xl": {"hidden_dim": 2048},
    # deeper encoder at base width: 2 extra replicated latent layers
    "deep": {"encoder_depth": 2},
    # the multi-task family recipe: wide core + deeper trunk
    "deep_wide": {"hidden_dim": 1024, "encoder_depth": 2},
}


def apply_model_preset(cfg: R2D2Config, name: Optional[str] = None) -> R2D2Config:
    """Overlay a MODEL_PRESETS entry onto `cfg` (default: its own
    cfg.model_preset field) and stamp the name, re-validating."""
    name = cfg.model_preset if name is None else name
    if name not in MODEL_PRESETS:
        raise ValueError(
            f"unknown model_preset {name!r}; one of {sorted(MODEL_PRESETS)}"
        )
    return cfg.replace(model_preset=name, **MODEL_PRESETS[name])


def parse_overrides(pairs) -> dict:
    """Parse CLI `--set key=value` pairs into typed replace() kwargs —
    the reference's edit-config.py workflow without editing files. Values
    are coerced by the dataclass field's type: int/float/bool/str scalars
    and comma-separated int tuples (e.g. obs_shape=64,64,3). Unknown keys
    raise with the full field list."""
    fields = {f.name: f for f in dataclasses.fields(R2D2Config)}
    out = {}
    for pair in pairs:
        if "=" not in pair:
            raise ValueError(f"--set expects key=value, got {pair!r}")
        key, _, raw = pair.partition("=")
        key = key.strip()
        if key not in fields:
            raise ValueError(
                f"unknown config field {key!r}; valid: {sorted(fields)}"
            )
        ftype = fields[key].type
        # unwrap Optional[...] (string annotations under future-import):
        # the inner type drives coercion; "none" selects None itself
        if isinstance(ftype, str) and ftype.startswith("Optional["):
            if raw.lower() == "none":
                out[key] = None
                continue
            ftype = ftype[len("Optional[") : -1]
        if ftype in ("int", int):
            out[key] = int(raw)
        elif ftype in ("float", float):
            out[key] = float(raw)
        elif ftype in ("bool", bool):
            if raw.lower() not in ("true", "false", "1", "0"):
                raise ValueError(f"{key} expects a bool, got {raw!r}")
            out[key] = raw.lower() in ("true", "1")
        elif "Tuple" in str(ftype):
            out[key] = tuple(int(v) for v in raw.split(","))
        else:  # str (and Optional[str]: pass through)
            out[key] = raw
    return out


def apply_cli_overrides(cfg, set_pairs=None, ablate_zero_state=False):
    """One resolution order for every demo/CLI: `--set` overrides first,
    then the zero-state ablation flag — so the flag's documented contract
    (burn_in=0 + zero_state_replay) always wins. Until round 5 the demos
    applied the flag first, and `--set burn_in_steps=N --ablate-zero-state`
    silently restored an N-step burn-in (the one affected artifact is
    recorded in runs/README.md, mc84_full_lru_zerostate)."""
    if set_pairs:
        cfg = cfg.replace(**parse_overrides(set_pairs))
    if ablate_zero_state:
        cfg = cfg.replace(burn_in_steps=0, zero_state_replay=True)
    return cfg
