"""Learner-throughput benchmark on the flagship configuration.

Measures sustained learner env-frames/sec/chip with the TPU-native pipeline:
device-resident replay data plane (replay/device_store.py), a fused jitted
update that gathers sequence windows in-jit from HBM, kilobyte-sized sample
coordinates as the only per-update host->device traffic, and asynchronous
draining of the priority round trip. Host work per update: one sum-tree
sample + one sum-tree update.

Rationale: on this hardware the host<->device link (not the MXU) bounds a
naive learner — shipping 38 MB batches from host replay measures the wire,
not the framework. The reference's design has exactly that shape (replay in
host RAM, batches over queues, reference worker.py:157,385-389).

Metric semantics (BASELINE.md): one update consumes batch x learning_steps
env transitions; frames = transitions x 4 (frameskip, reference
test.py:28,36). Reference implied learner throughput: 5.7 updates/s x 64 x
40 x 4 = 58,368 env-frames/s. North star: >= 100,000.

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": "env_frames/s", "vs_baseline": N}
"""

from __future__ import annotations

import json
import os
import queue
import sys
import threading
import time

import jax
import numpy as np

from r2d2_tpu.config import default_atari
from r2d2_tpu.learner import init_train_state, make_fused_multi_train_step
from r2d2_tpu.replay.block import Block
from r2d2_tpu.replay.device_store import DeviceReplayBuffer

BASELINE_FRAMES_PER_SEC = 58368.0  # BASELINE.md implied learner throughput


def synth_block(cfg, rng: np.random.Generator) -> Block:
    """A steady-state mid-episode block (burn-in carried, full length),
    built vectorized — replay-path realistic without stepping envs."""
    B, L, n, S = cfg.burn_in_steps, cfg.learning_steps, cfg.forward_steps, cfg.seqs_per_block
    size = cfg.block_length
    stored = B + size + 1
    forward = np.full(S, n, np.int32)
    forward[-1] = 1  # last sequence of a block cut bootstraps at +1
    return Block(
        obs=rng.integers(0, 255, size=(stored, *cfg.obs_shape), dtype=np.uint8),
        last_action=rng.integers(0, cfg.action_dim, size=stored).astype(np.uint8),
        last_reward=rng.normal(size=stored).astype(np.float32),
        action=rng.integers(0, cfg.action_dim, size=size).astype(np.uint8),
        n_step_reward=rng.normal(size=size).astype(np.float32),
        gamma=np.full(size, cfg.gamma**n, np.float32),
        hidden=(rng.normal(size=(S, 2, cfg.hidden_dim)) * 0.1).astype(np.float32),
        num_sequences=S,
        burn_in_steps=np.full(S, B, np.int32),
        learning_steps=np.full(S, L, np.int32),
        forward_steps=forward,
    )


def _precision_overrides(precision: str) -> dict:
    """--precision -> config fields. 'bf16' is the full mixed-precision
    plane (config.precision: bf16 matmuls + bf16 carry storage in replay /
    serve). 'fp32' is FULL float32 including compute — the vs_fp32 speedup
    denominator. Note the pre-policy bench rows ran a middle point (bf16
    matmuls, f32 state), so the fp32 arm here is slower than old rows."""
    if precision not in ("fp32", "bf16"):
        raise SystemExit(f"unknown precision {precision!r}")
    return {
        "precision": precision,
        "compute_dtype": "float32" if precision == "fp32" else "bfloat16",
    }


def _core_overrides(core: str, lru_chunk: int) -> dict:
    """--core/--lru-chunk -> config fields. 'lstm' is the headline default;
    'lru' selects the time-parallel core (models/lru.py), with lru_chunk>0
    picking its MXU triangular-matmul formulation — the round-4 MFU
    verdict's declared lever (runs/core_unroll_r4.jsonl: lru-c128 fastest
    at T=128, the closest measured row to the bench's T=85)."""
    if core == "lstm" and lru_chunk:
        raise SystemExit("--lru-chunk requires --core lru")
    return {"recurrent_core": core, "lru_chunk": lru_chunk if core == "lru" else 0}


def _system_cfg(E: int = 256, core: str = "lstm", lru_chunk: int = 0,
                precision: str = "bf16", priority_plane: str = "host",
                superstep: int = 1):
    """Shared full-system benchmark config: catch at Atari resolution
    (84x84, device-rendered; this image has no ALE and one host core —
    SURVEY.md section 2.4), full-size network. priority_plane/superstep
    select the round-9 arm: "device" moves the sum tree to HBM and runs
    sampling + priority write-back in-jit (megastep superstep, host
    re-enters every superstep*updates_per_dispatch updates)."""
    return default_atari().replace(
        priority_plane=priority_plane,
        superstep_dispatches=superstep,
        env_name="catch",
        action_dim=3,
        num_actors=E,
        **_precision_overrides(precision),
        **_core_overrides(core, lru_chunk),
        max_episode_steps=82,  # catch: ball lands after height-2 steps
        collector="device",
        replay_plane="device",
        updates_per_dispatch=16,
        # capacity counts SLOTS x block_length, but catch blocks hold only
        # 82 steps (one episode), so the effective transition capacity is
        # num_blocks x 82 = 82k — budget learning_starts against that
        buffer_capacity=400_000,
        learning_starts=40_000,
        training_steps=1_000_000,
        save_interval=1_000_000,  # no checkpoint I/O inside the window
    )


def recovery_main(precision: str = "fp32"):
    """Preemption-recovery benchmark: kill a small training run mid-stream
    with an injected SIGTERM (utils/faults.py — the deterministic stand-in
    for a real grace-window delivery), then measure the wall time from
    starting the resumed Trainer's construction to its first COMPLETED
    update. That interval is the full operational cost of a preemption:
    checkpoint restore + replay-snapshot restore + mid-run carry rehydrate
    + recompile + first sample/update. Reported as the standard BENCH row
    `recovery_to_first_update_s`."""
    import os
    import tempfile

    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.train import Trainer
    from r2d2_tpu.utils import faults

    workdir = tempfile.mkdtemp(prefix="bench_recovery_")
    # fp32 default: the recovery row's historical config. --precision bf16
    # additionally drills the bf16 snapshot round trip under preemption.
    cfg = tiny_test().replace(
        env_name="catch",
        **_precision_overrides(precision if precision != "both" else "bf16"),
        snapshot_replay=True,
        checkpoint_dir=os.path.join(workdir, "ckpt"),
        metrics_path=os.path.join(workdir, "metrics.jsonl"),
        training_steps=40,
        save_interval=10_000,  # only the preemption checkpoint exists
        learning_starts=48,
    )
    # phase 1: train until the injected SIGTERM cuts the run (update #6)
    faults.install(faults.FaultPlane(schedule={"trainer.update": {6: "sigterm"}}))
    try:
        trainer = Trainer(cfg)
        trainer.run_inline(env_steps_per_update=4)
        assert trainer.preempted, "injected SIGTERM did not preempt the run"
        cut_step = trainer._step
    finally:
        faults.uninstall()
    print(f"preempted at step {cut_step}; resuming...", file=sys.stderr)

    # phase 2: the measured recovery — construction-to-first-update
    t0 = time.time()
    resumed = Trainer(cfg, resume=True)
    m, step = resumed._one_update(resumed.plane.sample())
    jax.block_until_ready(resumed.state.params)
    recovery_s = time.time() - t0
    resumed.finish_updates()
    assert step == cut_step + 1
    print(
        json.dumps(
            {
                "metric": "recovery_to_first_update_s",
                "value": round(recovery_s, 3),
                "unit": "s",
                "cut_step": cut_step,
                "resumed_step": step,
                "loss": round(float(m["loss"]), 4),
                "core": cfg.recurrent_core,
                "precision": cfg.precision,
            }
        )
    )

    # phase 3: elastic recovery — the same drill across a CHANGED device
    # topology. A sharded dp=2 run is preempted, then resumed as a dp=1
    # device-plane run with cfg.reshard_on_resume: the measured interval
    # additionally pays the manifest check + slab regather + re-deal
    # (replay/reshard.py), the full cost of coming back on whatever the
    # scheduler hands out. Needs 2 devices; skipped (with a note) on 1.
    if len(jax.devices()) < 2:
        print(
            "skipping resume_across_topology_s: needs >= 2 devices",
            file=sys.stderr,
        )
        return
    workdir2 = tempfile.mkdtemp(prefix="bench_reshard_")
    cfg_sh = cfg.replace(
        replay_plane="sharded",
        dp_size=2,
        checkpoint_dir=os.path.join(workdir2, "ckpt"),
        metrics_path=os.path.join(workdir2, "metrics.jsonl"),
    )
    faults.install(faults.FaultPlane(schedule={"trainer.update": {6: "sigterm"}}))
    try:
        trainer = Trainer(cfg_sh)
        trainer.run_inline(env_steps_per_update=4)
        assert trainer.preempted, "injected SIGTERM did not preempt the run"
        cut_step = trainer._step
    finally:
        faults.uninstall()
    print(
        f"preempted sharded dp=2 at step {cut_step}; "
        "resuming on device dp=1...",
        file=sys.stderr,
    )
    cfg_dev = cfg_sh.replace(
        replay_plane="device", dp_size=1, reshard_on_resume=True
    )
    t0 = time.time()
    resumed = Trainer(cfg_dev, resume=True)
    m, step = resumed._one_update(resumed.plane.sample())
    jax.block_until_ready(resumed.state.params)
    reshard_s = time.time() - t0
    resumed.finish_updates()
    assert step == cut_step + 1
    print(
        json.dumps(
            {
                "metric": "resume_across_topology_s",
                "value": round(reshard_s, 3),
                "unit": "s",
                "cut_step": cut_step,
                "resumed_step": step,
                "saved_topology": "sharded dp=2",
                "resumed_topology": "device dp=1",
                "loss": round(float(m["loss"]), 4),
                "core": cfg.recurrent_core,
                "precision": cfg.precision,
            }
        )
    )


def fused_system_main(collect_every: int = 6, core: str = "lstm",
                      lru_chunk: int = 0, precision: str = "bf16"):
    """Full-system throughput via the fused megastep (megastep.py): ONE
    dispatch = K updates + a collection chunk every collect_every'th
    dispatch. No worker threads — the host only runs sum-tree bookkeeping
    between dispatches. Default collect_every=6 matches the threaded
    system benchmark's measured consumed:inserted ratio (~12:1) so the two
    modes are comparable like for like."""
    from r2d2_tpu.megastep import FusedSystemRunner
    from r2d2_tpu.train import Trainer

    cfg = _system_cfg(core=core, lru_chunk=lru_chunk,
                      precision="bf16" if precision == "both" else precision)
    trainer = Trainer(cfg)
    print(f"warmup: filling {cfg.learning_starts} transitions...", file=sys.stderr)
    t0 = time.time()
    trainer.warmup()
    trainer._start_time = time.time()
    print(f"warmup done in {time.time()-t0:.1f}s", file=sys.stderr)

    runner = FusedSystemRunner(
        cfg, trainer.net, trainer.fn_env, trainer.replay,
        trainer.actor.epsilons, trainer.actor.env_state, trainer.actor.key,
        collect_every=collect_every, sample_rng=trainer.sample_rng,
    )
    state = trainer.state
    # compile both dispatch variants (collect and update-only) outside the window
    state, m, _ = runner.step(state)
    if collect_every > 1:
        state, m, _ = runner.step(state)
    _ = int(np.asarray(state.step))

    target_seconds = 30.0
    n_updates = 0
    env0 = runner.total_env_steps
    t0 = time.time()
    while time.time() - t0 < target_seconds:
        state, m, _ = runner.step(state)
        n_updates += cfg.updates_per_dispatch
    _ = int(np.asarray(state.step))  # stream sync
    elapsed = time.time() - t0
    # finish() drains the final in-flight chunk's accounting (its dispatch
    # time is inside `elapsed`, so its steps belong in `env`)
    runner.finish()
    env = runner.total_env_steps - env0
    learner_fps = n_updates / elapsed * cfg.batch_size * cfg.learning_steps * 4
    collect_fps = env / elapsed * 4
    print(
        f"{n_updates} updates + {env} env steps in {elapsed:.1f}s "
        f"(loss {float(m['loss']):.4f}, collect_every={collect_every})",
        file=sys.stderr,
    )
    print(
        json.dumps(
            {
                "metric": "fused_system_learner_env_frames_per_sec_per_chip",
                "value": round(learner_fps, 1),
                "unit": "env_frames/s",
                "vs_baseline": round(learner_fps / BASELINE_FRAMES_PER_SEC, 3),
                "concurrent_collection_env_frames_per_sec": round(collect_fps, 1),
                "core": cfg.recurrent_core + (f"_c{cfg.lru_chunk}" if cfg.lru_chunk else ""),
                "precision": cfg.precision,
            }
        )
    )


def system_main(core: str = "lstm", lru_chunk: int = 0, precision: str = "bf16",
                priority_plane: str = "host", superstep: int = 1):
    """Full-system throughput: on-device collection (collect.py) and the
    K-update learner dispatch sharing ONE chip concurrently — the complete
    TPU-native R2D2 (actor + replay + learner) with no synthetic data.

    Env: catch at Atari resolution (84x84, device-rendered; this image has
    no ALE and one host core — SURVEY.md section 2.4), full-size network.
    Prints one JSON line with learner env-frames/s (the BASELINE.md metric)
    measured WHILE collection sustains its own rate on the same chip.

    priority_plane="device" is the round-9 A/B arm: sampling + priority
    write-back run in-jit over the HBM sum tree and the host re-enters
    every superstep*updates_per_dispatch updates, so the per-update host
    fence (stratified numpy sample before, D2H read-back + tree scatter
    after) leaves the loop."""
    from r2d2_tpu.train import Trainer

    cfg = _system_cfg(core=core, lru_chunk=lru_chunk,
                      precision="bf16" if precision == "both" else precision,
                      priority_plane=priority_plane, superstep=superstep)
    trainer = Trainer(cfg)
    print(f"warmup: filling {cfg.learning_starts} transitions...", file=sys.stderr)
    t0 = time.time()
    trainer.warmup()
    trainer._start_time = time.time()
    print(f"warmup done in {time.time()-t0:.1f}s", file=sys.stderr)

    stop = threading.Event()

    def actor_loop():
        while not stop.is_set():
            trainer.actor.step()

    # compile both paths before the window
    item = trainer.plane.sample()
    m, _ = trainer._one_update(item)
    _ = int(np.asarray(trainer.state.step))

    at = threading.Thread(target=actor_loop, daemon=True)
    at.start()
    target_seconds = 30.0
    steps0, env0 = trainer._step, trainer.replay.env_steps
    t0 = time.time()
    while time.time() - t0 < target_seconds:
        m, _ = trainer._one_update(trainer.plane.sample())
    _ = int(np.asarray(trainer.state.step))  # stream sync
    # snapshot BOTH counters at the same instant as elapsed: a collector
    # chunk landing during stop/join must not count toward the window
    elapsed = time.time() - t0
    env = trainer.replay.env_steps - env0
    upd = trainer._step - steps0
    stop.set()
    at.join(timeout=10.0)
    trainer.finish_updates()  # apply the final in-flight priority chunk
    learner_fps = upd / elapsed * cfg.batch_size * cfg.learning_steps * 4
    collect_fps = env / elapsed * 4
    print(
        f"{upd} updates + {env} env steps in {elapsed:.1f}s "
        f"(loss {float(m['loss']):.4f})",
        file=sys.stderr,
    )
    print(
        json.dumps(
            {
                "metric": "full_system_learner_env_frames_per_sec_per_chip",
                "value": round(learner_fps, 1),
                "unit": "env_frames/s",
                "vs_baseline": round(learner_fps / BASELINE_FRAMES_PER_SEC, 3),
                "concurrent_collection_env_frames_per_sec": round(collect_fps, 1),
                "core": cfg.recurrent_core + (f"_c{cfg.lru_chunk}" if cfg.lru_chunk else ""),
                "precision": cfg.precision,
                "priority_plane": cfg.priority_plane,
                "superstep_dispatches": cfg.superstep_dispatches,
            }
        )
    )


def main(
    cfg=None,
    K: int = 16,
    metric: str = "learner_env_frames_per_sec_per_chip",
    frame_multiplier: int = 4,
    baseline: float = BASELINE_FRAMES_PER_SEC,
    core: str = "lstm",
    lru_chunk: int = 0,
    batch: int = 0,
    emit: bool = True,
    precision: str = "bf16",
):
    """frame_multiplier: env frames per env step — 4 for Atari (frameskip,
    reference test.py:28,36), 1 for envs without frameskip. baseline: the
    denominator for vs_baseline. core/lru_chunk select the recurrent core
    (_core_overrides); batch > 0 overrides batch_size (the MFU
    shape-granularity probe — frames/s scales with batch by construction,
    so cross-batch rows compare updates/s x batch, not the headline).
    precision selects the mixed-precision arm (_precision_overrides;
    ignored when an explicit cfg is passed — the row reports
    cfg.precision either way).
    Returns the result row; emit=False suppresses the JSON print so
    matrix drivers (learner_matrix_main) keep exactly one line on
    stdout."""
    cfg = cfg or default_atari().replace(
        buffer_capacity=100_000,  # 250 block slots ~= 0.77 GB HBM obs store
        **_precision_overrides(precision),
        **_core_overrides(core, lru_chunk),
    )
    if batch:
        cfg = cfg.replace(batch_size=batch)
    rng = np.random.default_rng(0)
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})", file=sys.stderr)

    t0 = time.time()
    replay = DeviceReplayBuffer(cfg)
    n_blocks = cfg.learning_starts // cfg.block_length + 5
    for _ in range(n_blocks):
        block = synth_block(cfg, rng)
        prios = rng.uniform(0.5, 2.0, size=cfg.seqs_per_block).astype(np.float32)
        replay.add_block(block, prios, None)
    jax.block_until_ready(replay.stores["obs"])
    assert replay.can_sample()
    print(
        f"replay filled: {len(replay)} transitions ({n_blocks} block uploads) "
        f"in {time.time()-t0:.1f}s",
        file=sys.stderr,
    )

    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    # K updates per dispatch: the per-launch host overhead is amortized
    # K-fold by scanning K updates inside one call
    # (learner.make_fused_multi_train_step; exact-equivalence tested).
    multi_step = make_fused_multi_train_step(cfg, net, K)
    sample_rng = np.random.default_rng(1)

    # prefetch thread: K tree draws stacked into one upload per array
    idx_q: "queue.Queue" = queue.Queue(maxsize=4)
    prio_q: "queue.Queue" = queue.Queue(maxsize=8)
    stop = threading.Event()

    def sampler():
        while not stop.is_set():
            draws = [replay.sample_indices(sample_rng) for _ in range(K)]
            dev_idx = (
                jax.device_put(np.stack([d.b for d in draws])),
                jax.device_put(np.stack([d.s for d in draws])),
                jax.device_put(np.stack([d.is_weights for d in draws])),
            )
            while not stop.is_set():
                try:
                    idx_q.put((dev_idx, draws), timeout=0.5)
                    break
                except queue.Full:
                    pass

    def drainer():
        # one readback per dispatch: the (K, B) priorities arrive in a
        # single transfer whose latency overlaps continued dispatching,
        # then land on the host tree row by row (bounded lag)
        while not stop.is_set():
            try:
                prios, draws = prio_q.get(timeout=0.5)
            except queue.Empty:
                continue
            stacked = np.asarray(prios)
            for row, d in zip(stacked, draws):
                replay.update_priorities(d.idxes, row, d.old_ptr, d.old_advances)

    threads = [
        threading.Thread(target=sampler, daemon=True),
        threading.Thread(target=drainer, daemon=True),
    ]
    for t in threads:
        t.start()

    def one_chunk():
        nonlocal state
        (b, s, w), draws = idx_q.get()
        # run_with_stores: dispatch under the buffer lock so a concurrent
        # add_block's donated swap can't invalidate the arrays mid-dispatch
        state, metrics, priorities = replay.run_with_stores(
            lambda stores: multi_step(state, stores, b, s, w)
        )
        # start the device->host transfer immediately: transfers for
        # successive chunks pipeline through the link, so the drainer's
        # later np.asarray finds the data already (or nearly) arrived
        # instead of paying the full round trip serially per chunk
        priorities.copy_to_host_async()
        prio_q.put((priorities, draws))
        return metrics

    def sync() -> int:
        # a host readback of the step counter: it waits for the whole
        # dispatch stream (the donated state threads through every chunk)
        return int(np.asarray(state.step))

    # compile + warm
    t0 = time.time()
    m = one_chunk()
    sync()
    print(f"compile+first chunk: {time.time()-t0:.1f}s loss={float(m['loss']):.4f}", file=sys.stderr)
    for _ in range(4):
        m = one_chunk()
    sync()

    # timed run: dispatch for the window, then sync so `elapsed` covers the
    # completion of every counted update (dispatch alone proves nothing)
    target_seconds = 20.0
    n_updates = 0
    t0 = time.time()
    while time.time() - t0 < target_seconds:
        m = one_chunk()
        n_updates += K
    sync()
    elapsed = time.time() - t0
    final_loss = float(m["loss"])

    updates_per_sec = n_updates / elapsed
    frames_per_sec = (
        updates_per_sec * cfg.batch_size * cfg.learning_steps * frame_multiplier
    )
    print(
        f"{n_updates} updates in {elapsed:.1f}s = {updates_per_sec:.2f} updates/s "
        f"(final loss {final_loss:.4f})",
        file=sys.stderr,
    )
    stop.set()
    for t in threads:
        t.join(timeout=5.0)

    row = {
        "metric": metric,
        "value": round(frames_per_sec, 1),
        "unit": "env_frames/s",
        "vs_baseline": round(frames_per_sec / baseline, 3),
        "core": cfg.recurrent_core + (f"_c{cfg.lru_chunk}" if cfg.lru_chunk else ""),
        "precision": cfg.precision,
        "batch": cfg.batch_size,
        "updates_per_sec": round(updates_per_sec, 2),
    }
    if emit:
        print(json.dumps(row))
    return row


def learner_matrix_main(core: str = "lstm", lru_chunk: int = 0, batch: int = 0,
                        precision: str = "bf16"):
    """Learner-mode driver: the headline is the BEST row of the batch
    matrix, not a fixed batch size. Round 5 measured B=128 at 1.279M
    env-frames/s — 27% above the B=64 row the headline used to report —
    so pinning B=64 understated the chip. An explicit --batch still runs
    exactly that one shape; batch=0 sweeps the matrix and emits one JSON
    line carrying the winner (with its batch size) plus every row.

    The headline always carries `vs_fp32`: under bf16 a silent fp32
    reference runs at the winning batch so the speedup is measured at the
    same shape; --precision both additionally attaches the fp32 row."""
    arm = "bf16" if precision == "both" else precision
    batches = (batch,) if batch else (64, 128)
    rows = [
        main(core=core, lru_chunk=lru_chunk, batch=bs, emit=False, precision=arm)
        for bs in batches
    ]
    best = max(rows, key=lambda r: r["value"])
    if arm == "fp32":
        fp32_row, vs_fp32 = None, 1.0
    else:
        fp32_row = main(
            core=core, lru_chunk=lru_chunk, batch=best["batch"],
            emit=False, precision="fp32",
        )
        vs_fp32 = best["value"] / fp32_row["value"]
        print(
            f"[precision] bf16 {best['value']:.0f} vs fp32 "
            f"{fp32_row['value']:.0f} env-frames/s = {vs_fp32:.2f}x "
            f"at batch {best['batch']}",
            file=sys.stderr,
        )
    out = {
        **best,
        "metric": "learner_env_frames_per_sec_per_chip",
        "vs_fp32": round(vs_fp32, 3),
    }
    if not batch:
        out["matrix"] = [
            {
                "batch": r["batch"],
                "value": r["value"],
                "updates_per_sec": r["updates_per_sec"],
            }
            for r in rows
        ]
    if precision == "both" and fp32_row is not None:
        out["fp32"] = {
            "batch": fp32_row["batch"],
            "value": fp32_row["value"],
            "updates_per_sec": fp32_row["updates_per_sec"],
        }
    print(json.dumps(out))


def tiered_main(
    core: str = "lstm",
    lru_chunk: int = 0,
    batch: int = 0,
    capacity: int = 2_000_000,
    K: int = 16,
    precision: str = "bf16",
):
    """Tiered-plane learner throughput AT FULL REPLAY CAPACITY: the store
    holds `capacity` transitions in host RAM (2M default — the paper's
    spec, 20x what the HBM plane's bench shape holds) while the staging
    pipeline (replay/tiered_store.py) hides the host->HBM copies behind
    the K-update scan. The JSON row reports updates/s AND the measured
    H2D overlap fraction — the win condition is the copies disappearing
    behind compute, not just the headline rate.

    The store is filled to learning_starts only (np.zeros pages beyond the
    filled prefix stay unmapped): sample/gather cost depends on the tree
    and window shapes, not on how much of the 2M ring is resident."""
    from r2d2_tpu.learner import make_stacked_batch_train_step
    from r2d2_tpu.replay.tiered_store import TieredPrefetchPipeline, TieredReplayBuffer
    from r2d2_tpu.utils.profiling import TransferTimer

    cfg = default_atari().replace(
        buffer_capacity=capacity,
        replay_plane="tiered",
        updates_per_dispatch=K,
        **_precision_overrides("bf16" if precision == "both" else precision),
        **_core_overrides(core, lru_chunk),
    )
    if batch:
        cfg = cfg.replace(batch_size=batch)
    cfg.validate()
    rng = np.random.default_rng(0)
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})", file=sys.stderr)

    t0 = time.time()
    replay = TieredReplayBuffer(cfg)
    n_blocks = cfg.learning_starts // cfg.block_length + 5
    for _ in range(n_blocks):
        block = synth_block(cfg, rng)
        prios = rng.uniform(0.5, 2.0, size=cfg.seqs_per_block).astype(np.float32)
        replay.add_block(block, prios, None)
    assert replay.can_sample()
    print(
        f"tiered replay: {len(replay)} transitions resident of "
        f"{capacity} capacity ({n_blocks} blocks) in {time.time()-t0:.1f}s",
        file=sys.stderr,
    )

    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    multi_step = make_stacked_batch_train_step(cfg, net, K)
    timer = TransferTimer()
    pipe = TieredPrefetchPipeline(
        replay, np.random.default_rng(1), K, timer=timer
    )
    pending = [None]

    def one_chunk():
        nonlocal state
        chunk = pipe.get()
        state, metrics, priorities = multi_step(state, chunk.batch)
        priorities.copy_to_host_async()
        prev, pending[0] = pending[0], (priorities, chunk)
        if prev is not None:
            prios, c = prev
            for row, idx in zip(np.asarray(prios), c.idxes):
                replay.update_priorities(idx, row, c.old_ptr, c.old_advances)
        return metrics

    def sync() -> int:
        return int(np.asarray(state.step))

    t0 = time.time()
    m = one_chunk()
    sync()
    print(f"compile+first chunk: {time.time()-t0:.1f}s loss={float(m['loss']):.4f}", file=sys.stderr)
    for _ in range(4):
        m = one_chunk()
    sync()
    timer.reset()  # overlap window excludes compile/warmup chunks

    target_seconds = 20.0
    n_updates = 0
    t0 = time.time()
    while time.time() - t0 < target_seconds:
        m = one_chunk()
        n_updates += K
    sync()
    elapsed = time.time() - t0
    final_loss = float(m["loss"])
    pipe.stop()
    if pending[0] is not None:  # final in-flight priority chunk
        prios, c = pending[0]
        for row, idx in zip(np.asarray(prios), c.idxes):
            replay.update_priorities(idx, row, c.old_ptr, c.old_advances)

    updates_per_sec = n_updates / elapsed
    frames_per_sec = updates_per_sec * cfg.batch_size * cfg.learning_steps * 4
    print(
        f"{n_updates} updates in {elapsed:.1f}s = {updates_per_sec:.2f} updates/s "
        f"(final loss {final_loss:.4f})",
        file=sys.stderr,
    )
    print(
        json.dumps(
            {
                "metric": "tiered_learner_env_frames_per_sec_per_chip",
                "value": round(frames_per_sec, 1),
                "unit": "env_frames/s",
                "vs_baseline": round(frames_per_sec / BASELINE_FRAMES_PER_SEC, 3),
                "updates_per_sec": round(updates_per_sec, 2),
                "replay_capacity_transitions": capacity,
                "batch": cfg.batch_size,
                "core": cfg.recurrent_core + (f"_c{cfg.lru_chunk}" if cfg.lru_chunk else ""),
                "precision": cfg.precision,
                **timer.stats(),
            }
        )
    )


def _serve_load(cfg, sessions: int, seconds: float, label: str = "",
                arrival_rate: float = 0.0, slo_ms: float = 50.0,
                devices: int = 1) -> dict:
    """One serving-plane load arm against the full-size network through
    r2d2_tpu.serve, with a checkpoint hot-reload fired mid-window to prove
    reloads don't dent the latency tail.

    Two load shapes:

    - `arrival_rate > 0` — OPEN-LOOP (the honest overload measurement,
      and the default): a Poisson arrival process at `arrival_rate`
      requests/s over a session population sized ≫ the cache capacity
      (capacity = sessions/8, spill slab = 2x sessions), so the LRU tier
      churns and spill/promote round trips run under live traffic. Open
      loop means arrivals do NOT slow down when the server does — queueing
      delay lands in the latency numbers instead of silently throttling
      the offered load (closed-loop coordination omission). Rejected
      requests (full queue) count as SLO misses, not as absent samples.
    - `arrival_rate == 0` — the legacy CLOSED-LOOP arm: `sessions`
      CatchHostEnv threads each submit-then-wait in lockstep with their
      episode stream (cache sized 2x sessions, no spill churn).

    Either way the first `min(2s, 20% of window)` of requests is a
    WARM-UP window discarded from percentiles/SLO/requests-per-sec (its
    request count rides in the row as `warmup_requests`), so stragglers
    of first-batch compilation and cache fill don't pollute the tail.

    `devices > 1` serves through MultiDeviceServer replicas with
    session-affinity routing instead of a single PolicyServer.

    Returns the measured numbers; serve_main decides which arm is the
    headline. `label` names the arm in stderr progress lines (the int8
    arm runs at cfg.precision bf16, so precision alone is ambiguous)."""
    import os
    import shutil
    import tempfile
    from concurrent.futures import TimeoutError as FutureTimeout

    from r2d2_tpu.envs.catch import CatchHostEnv
    from r2d2_tpu.serve import (
        LocalClient,
        MultiDeviceServer,
        PolicyServer,
        QueueFullError,
        ServeConfig,
    )
    from r2d2_tpu.utils.checkpoint import save_checkpoint

    open_loop = arrival_rate > 0.0
    if open_loop:
        # sessions ≫ capacity: the HBM hot set holds a fraction of the
        # population, the rest live in (and return from) the host slab
        cache_capacity = max(32, sessions // 8)
        cfg = cfg.replace(
            serve_spill=max(cfg.serve_spill, 2 * sessions)
        ).validate()
    else:
        cache_capacity = max(2 * sessions, 64)
    if devices > 1:
        cfg = cfg.replace(serve_devices=devices).validate()
    serve_cfg = ServeConfig(
        buckets=(2, 4, 8, 16, 32),
        max_wait_ms=2.0,
        cache_capacity=cache_capacity,
        poll_interval_s=0.2,
    )
    label = label or cfg.precision
    tmp = tempfile.mkdtemp(prefix="serve_bench_")
    ckpt_dir = os.path.join(tmp, "ckpt")
    try:
        if devices > 1:
            server = MultiDeviceServer(cfg, serve_cfg, checkpoint_dir=ckpt_dir)
        else:
            server = PolicyServer(cfg, serve_cfg, checkpoint_dir=ckpt_dir)
        save_checkpoint(ckpt_dir, server._template, 0, 0.0)  # step-0 series
        t0 = time.perf_counter()
        server.warmup()
        print(
            f"[serve:{label}] warmup (all buckets x {devices} devices) in "
            f"{time.perf_counter() - t0:.1f}s",
            file=sys.stderr,
        )
        server.start()
        client = LocalClient(server)
        stop = threading.Event()
        # (submit time rel. to window start, latency seconds | None,
        # error class | None); appends are GIL-atomic, done-callbacks run
        # on the serve loop. submitted[0] vs len(records) at the end is
        # the timeout class: offered requests whose future never resolved.
        records: list = []
        submitted = [0]
        bench_t0 = time.perf_counter()

        def session_loop(i: int) -> None:
            env = CatchHostEnv(seed=i)
            sid = f"bench-{i}"
            obs, reward, reset = env.reset(), 0.0, True
            while not stop.is_set():
                t = time.perf_counter()
                submitted[0] += 1
                try:
                    res = client.act(sid, obs, reward=reward, reset=reset)
                except QueueFullError:
                    records.append((t - bench_t0, None, "rejected"))
                    continue  # re-offer the same step next loop
                except FutureTimeout:
                    records.append((t - bench_t0, None, "timeout"))
                    continue
                except Exception:
                    records.append((t - bench_t0, None, "transport"))
                    continue
                records.append((t - bench_t0, time.perf_counter() - t, None))
                obs, reward, done, _ = env.step(res.action)
                reset = done
                if done:
                    obs, reward = env.reset(), 0.0

        def arrival_loop() -> None:
            # Poisson process: exponential inter-arrival gaps at the target
            # rate; each arrival picks a uniform session and fires one
            # non-blocking submit, latency captured by the done callback
            rng = np.random.default_rng(1234)
            session_obs: dict = {}
            seen: set = set()
            next_t = time.perf_counter()
            while not stop.is_set():
                next_t += rng.exponential(1.0 / arrival_rate)
                delay = next_t - time.perf_counter()
                if delay > 0 and stop.wait(delay):
                    break
                i = int(rng.integers(0, sessions))
                obs = session_obs.get(i)
                if obs is None:
                    obs = rng.integers(0, 255, cfg.obs_shape, dtype=np.uint8)
                    session_obs[i] = obs
                sid = f"bench-{i}"
                reset = sid not in seen
                seen.add(sid)
                t_sub = time.perf_counter()
                submitted[0] += 1
                fut = server.submit(sid, obs, reward=0.0, reset=reset)

                def _done(f, t_sub=t_sub):
                    exc = f.exception()
                    if exc is None:
                        rec = (t_sub - bench_t0,
                               time.perf_counter() - t_sub, None)
                    elif isinstance(exc, QueueFullError):
                        rec = (t_sub - bench_t0, None, "rejected")
                    else:
                        rec = (t_sub - bench_t0, None, "transport")
                    records.append(rec)

                fut.add_done_callback(_done)

        if open_loop:
            threads = [threading.Thread(target=arrival_loop, daemon=True)]
        else:
            threads = [
                threading.Thread(target=session_loop, args=(i,), daemon=True)
                for i in range(sessions)
            ]
        for t in threads:
            t.start()
        # mid-window: publish a new checkpoint so the watcher hot-reloads
        # under live traffic
        time.sleep(seconds / 2)
        import jax.numpy as jnp

        bumped = server._template.replace(step=jnp.asarray(100, jnp.int32))
        save_checkpoint(ckpt_dir, bumped, 0, 0.0)
        time.sleep(seconds / 2)
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        time.sleep(0.5)  # let in-flight open-loop futures resolve
        elapsed = time.perf_counter() - bench_t0
        server.check()
        stats = server.stats()
        server.stop()

        warmup_s = min(2.0, 0.2 * seconds)
        warmup_requests = sum(1 for t_sub, _, _ in records if t_sub < warmup_s)
        measured = [r for r in records if r[0] >= warmup_s]
        ok = np.sort(np.asarray([lat for _, lat, _ in measured if lat is not None]))
        # per-class failure breakdown (not one lumped count): rejected =
        # shed/full queue, timeout = a future that never resolved within
        # the client deadline (or at all), transport = everything else
        errors = {"rejected": 0, "timeout": 0, "transport": 0}
        for _, _, err in measured:
            if err is not None:
                errors[err] += 1
        errors["timeout"] += max(submitted[0] - len(records), 0)
        errors_total = sum(errors.values())
        rps = ok.size / max(elapsed - warmup_s, 1e-9)
        if ok.size:
            p50, p95, p99 = (
                float(np.percentile(ok, p) * 1e3) for p in (50, 95, 99)
            )
        else:
            p50 = p95 = p99 = float("nan")
        # SLO attainment over everything offered post-warmup: a rejected
        # or failed request is a miss, not a dropped sample
        attained = int(np.count_nonzero(ok <= slo_ms / 1e3))
        slo_attainment = attained / max(len(measured), 1)
        print(
            f"[serve:{label}] {ok.size} requests over {sessions} sessions "
            f"in {elapsed:.1f}s ({'open' if open_loop else 'closed'}-loop, "
            f"warmup={warmup_requests}, errors={errors_total} {errors}, "
            f"reloads={stats['reloads']}, occupancy="
            f"{stats['mean_batch_occupancy']:.1f}, "
            f"spills={stats['cache_spills']}, "
            f"promotes={stats['cache_promotes']})",
            file=sys.stderr,
        )
        return {
            "value": round(rps, 1),
            "p50_latency_ms": round(p50, 2),
            "p95_latency_ms": round(p95, 2),
            "p99_latency_ms": round(p99, 2),
            "load_mode": "open" if open_loop else "closed",
            "arrival_rate": arrival_rate,
            "slo_ms": slo_ms,
            "slo_attainment": round(slo_attainment, 4),
            "warmup_requests": warmup_requests,
            "errors": errors,
            "errors_total": errors_total,
            "rejected": stats["rejected"],
            "serve_devices": devices,
            "mean_batch_occupancy": round(stats["mean_batch_occupancy"], 2),
            "bucket_fill": round(stats["bucket_fill"], 3),
            "reloads": stats["reloads"],
            "trace_count": stats["trace_count"],
            # session-tier traffic (serve/state_cache.py stats)
            "cache_capacity": stats["cache_capacity"],
            "cache_hit_rate": round(stats["cache_hit_rate"], 4),
            "cache_spills": stats["cache_spills"],
            "cache_promotes": stats["cache_promotes"],
            "cache_readmits": stats["cache_readmits"],
            "cache_spill_evictions": stats["cache_spill_evictions"],
            "spill_sessions": stats["spill_sessions"],
            # carry-cache precision footprint
            "cache_dtype": stats["cache_dtype"],
            "session_carry_bytes": stats["session_carry_bytes"],
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _arm_q_drift(cfg, arm: str, steps: int = 8, batch: int = 8) -> float:
    """A degradation arm's quality column: max |q_arm - q_fp| / max |q_fp|
    over a short recurrent act stream — both arms fed IDENTICAL inputs
    (including the fp arm's greedy actions) so the only difference is the
    arm's weight transform (int8 round-trip, or the weight-only bf16
    cast), compounding through the carry exactly as it does in a served
    session. Deterministic; independent of load traffic. Arms that leave
    the weights untouched ("full", "admit") are exactly 0 by definition."""
    import jax.numpy as jnp

    if arm in ("full", "admit"):
        return 0.0
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    params = state.params
    if arm == "int8":
        from r2d2_tpu.ops.quantize import dequantize_tree, quantize_tree

        deq = dequantize_tree(quantize_tree(params)[0])
    elif arm == "bf16":
        # the served bf16 arm keeps the leaves AS bf16 (the model's own
        # dtype promotion upcasts at compute) — probe exactly that
        deq = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
            params,
        )
    else:
        raise ValueError(f"unknown arm {arm!r}")
    act = jax.jit(
        lambda p, o, la, lr, c: net.apply(p, o, la, lr, c, method=net.act)
    )
    rng = np.random.default_rng(0)
    H = cfg.hidden_dim
    carry_fp = (jnp.zeros((batch, H), jnp.float32), jnp.zeros((batch, H), jnp.float32))
    carry_q = (jnp.zeros((batch, H), jnp.float32), jnp.zeros((batch, H), jnp.float32))
    la = jnp.zeros((batch,), jnp.int32)
    drift = scale = 0.0
    for _ in range(steps):
        obs = jnp.asarray(
            rng.integers(0, 255, (batch, *cfg.obs_shape), dtype=np.uint8)
        )
        lr = jnp.asarray(rng.normal(size=batch).astype(np.float32))
        q_fp, carry_fp = act(params, obs, la, lr, carry_fp)
        q_q, carry_q = act(deq, obs, la, lr, carry_q)
        drift = max(drift, float(jnp.max(jnp.abs(q_q - q_fp))))
        scale = max(scale, float(jnp.max(jnp.abs(q_fp))))
        la = jnp.argmax(q_fp, axis=-1).astype(jnp.int32)
    return drift / max(scale, 1e-9)


def _int8_q_drift(cfg, steps: int = 8, batch: int = 8) -> float:
    """The serve_int8 row's historical drift column (see _arm_q_drift)."""
    return _arm_q_drift(cfg, "int8", steps=steps, batch=batch)


def scenarios_main(
    core: str = "lstm",
    lru_chunk: int = 0,
    sessions: int = 64,
    seconds: float = 4.0,
    base_rate: float = 100.0,
    slo_ms: float = 50.0,
    out_path: str = "",
    seed: int = 0,
):
    """The scenario x rung readiness matrix (ROADMAP item 5): every
    built-in traffic scenario (steady control, diurnal ramp, flash crowd,
    Pareto-tailed sessions, slow clients, mid-scenario replica kill —
    serve/scenarios.py) against every degradation-ladder rung
    (full / admit / bf16 / int8 — serve/degrade.py), each cell reporting
    p99 latency, SLO attainment, per-class error breakdown, the rung's
    quality cost (`q_drift_vs_fp32`, the deterministic _arm_q_drift
    probe), and `sessions_lost` (kill-scenario migrations that found no
    spill room — the number that must stay 0).

    One TWO-REPLICA fleet per rung (both replicas on the first local
    device when only one is visible — affinity, migration, and the kill
    path are device-count-independent), controller PINNED at the rung so
    the cell measures one ladder position, and the kill scenario runs
    LAST on each fleet (it retires a replica for good). Emits one
    `serve_scenario_matrix` row; --scenario-out also writes it as the
    BENCH_r11-style readiness report."""
    from r2d2_tpu.serve import (
        RUNGS,
        MultiDeviceServer,
        ScenarioRunner,
        ServeConfig,
        builtin_scenarios,
    )

    cfg = _system_cfg(core=core, lru_chunk=lru_chunk, precision="fp32")
    cfg = cfg.replace(
        # per-replica slab sized so one scenario's whole session
        # population (slot recycling included) fits a SURVIVOR's slab
        # after a kill-migration wave — sessions_lost must stay 0
        serve_spill=4 * sessions,
        serve_degrade=True,
        serve_degrade_slo_ms=slo_ms,
    ).validate()
    serve_cfg = ServeConfig(
        buckets=(2, 4, 8, 16, 32),
        max_wait_ms=2.0,
        cache_capacity=max(32, sessions // 2),
        poll_interval_s=0.5,
    )
    d0 = jax.local_devices()[0]
    drifts = {rung: round(_arm_q_drift(cfg, rung), 6) for rung in RUNGS}
    specs = builtin_scenarios(
        base_rate=base_rate, duration_s=seconds, sessions=sessions, seed=seed
    )
    cells = []
    for rung in RUNGS:
        # a fresh fleet per rung: the kill scenario retires a replica and
        # the ladder state must not leak across rungs
        server = MultiDeviceServer(cfg, serve_cfg, devices=[d0, d0])
        server.degrade.pin(rung)  # warmup traces the PINNED arm's step
        t0 = time.perf_counter()
        server.warmup()
        print(
            f"[scenarios:{rung}] warmup in {time.perf_counter() - t0:.1f}s "
            f"(q_drift_vs_fp32={drifts[rung]})",
            file=sys.stderr,
        )
        server.start(watch_checkpoints=False)
        try:
            for spec in specs:
                before = server.stats()
                server.degrade.reset_window()
                row = ScenarioRunner(server, spec, slo_ms=slo_ms).run()
                after = server.stats()
                cell = {
                    "rung": rung,
                    "q_drift_vs_fp32": drifts[rung],
                    **row,
                    "sessions_lost": after["sessions_lost"]
                    - before["sessions_lost"],
                    "sessions_migrated": after["sessions_migrated"]
                    - before["sessions_migrated"],
                    "shed": after["shed"] - before["shed"],
                    "serve_arm": after["serve_arm"],
                }
                cells.append(cell)
                print(
                    f"[scenarios:{rung}] {spec.name}: "
                    f"p99={cell['p99_latency_ms'] and round(cell['p99_latency_ms'], 1)}ms "
                    f"slo={cell['slo_attainment']:.3f} "
                    f"errors={cell['errors_total']} "
                    f"lost={cell['sessions_lost']} "
                    f"migrated={cell['sessions_migrated']}",
                    file=sys.stderr,
                )
        finally:
            server.stop()
    report = {
        "metric": "serve_scenario_matrix",
        "unit": "matrix",
        "value": len(cells),
        "rungs": list(RUNGS),
        "scenarios": [s.name for s in specs],
        "slo_ms": slo_ms,
        "base_rate": base_rate,
        "duration_s": seconds,
        "sessions": sessions,
        "seed": seed,
        "q_drift_vs_fp32": drifts,
        "cells": cells,
        "core": cfg.recurrent_core
        + (f"_c{cfg.lru_chunk}" if cfg.lru_chunk else ""),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        print(f"[scenarios] readiness report -> {out_path}", file=sys.stderr)
    print(json.dumps(report))


def autoscale_main(
    core: str = "lstm",
    lru_chunk: int = 0,
    sessions: int = 64,
    seconds: float = 16.0,
    base_rate: float = 0.0,
    slo_ms: float = 50.0,
    out_path: str = "",
    seed: int = 0,
):
    """Elastic-fleet economics (ROADMAP item 1): the PR 11 diurnal
    scenario against the AUTOSCALED fleet (starts at min_replicas=1,
    grows under sustained SLO pressure, drains back when healthy —
    serve/autoscale.py) and against a PEAK-SIZED STATIC fleet of
    max_replicas=2, same seeded arrival trace for both.

    base_rate=0 first calibrates one replica's capacity with a short
    saturating steady probe, then offers base = capacity/2.6 so the 3x
    diurnal crest (~1.15x one replica) forces a scale-up while the edges
    sit comfortably inside one replica. The elastic arm must ride through >= 1
    scale-up AND >= 1 scale-down with zero lost sessions (the drain
    migrates through the spill tier), attain the SLO no worse than the
    static fleet, and spend fewer chip-seconds (the integral of active
    replicas over the measured horizon; the static fleet holds 2 for all
    of it). Emits one `serve_autoscale_diurnal` row -> BENCH_r17.json.

    Replicas share the first local device when only one is visible —
    control-loop behavior (signals, dwells, migration, interlock) is
    device-count-independent; only the chip-seconds ECONOMICS read
    differently on real multi-device hardware (noted in the row)."""
    from r2d2_tpu.serve import (
        MultiDeviceServer,
        ScenarioRunner,
        ScenarioSpec,
        ServeConfig,
    )
    from r2d2_tpu.utils.compilation_cache import enable_compilation_cache

    # the probe fleet compiles every bucket shape first; with a persistent
    # cache in effect (utils/compilation_cache.py's one directory rule: a
    # TPU, or JAX_COMPILATION_CACHE_DIR set — a CPU run without the
    # variable has none and pays each warmup's compiles), BOTH arms'
    # warmups and — critically — the mid-scenario add_replica warmup
    # become cache hits instead of stealing the serving core for whole
    # seconds at the crest. Floor at 0: these bucket programs compile in
    # tens of milliseconds each, far under the default persistence
    # threshold, but a dozen of them mid-run is exactly the scale-up
    # latency this bench is measuring
    if enable_compilation_cache():
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    cfg0 = _system_cfg(core=core, lru_chunk=lru_chunk, precision="fp32")
    cfg0 = cfg0.replace(
        # drain-wave sizing rule: a scale-down exports the victim's WHOLE
        # row set — live sessions plus every churned-out session no
        # client ever disconnected — into one survivor's slab, so the
        # slab must hold the scenario's full distinct-session population
        # (events / session_mean_requests, with slack), not just the
        # concurrent slots. Undersize it and a mid-traffic drain reports
        # real rows as sessions_lost.
        serve_spill=16 * sessions,
        serve_degrade=True,
        serve_degrade_slo_ms=slo_ms,
    )
    serve_cfg = ServeConfig(
        # two shapes, not five: a scale-up warms every bucket MID-CREST
        # on the serving silicon, so each extra bucket is stolen
        # capacity exactly when the fleet can least afford it
        buckets=(4, 16),
        max_wait_ms=2.0,
        # a tight queue bound makes queue_frac a fast PREDICTIVE pressure
        # signal (the autoscaler's primary scale-up trigger): 25% of 64
        # is a backlog the replica still clears inside the SLO, so the
        # scale-up fires before attainment pays for it
        queue_depth=64,
        # the whole session population must fit ONE replica's HBM rows:
        # the elastic arm starts at a single replica, and judging it on
        # spill-slab thrash would measure the cache, not the autoscaler
        cache_capacity=max(32, sessions),
        poll_interval_s=0.5,
    )
    d0 = jax.local_devices()[0]

    if base_rate <= 0:
        # capacity probe: saturate ONE replica (degrade off: no shedding
        # valve) and read the answered throughput as its capacity
        probe_cfg = cfg0.replace(serve_degrade=False).validate()
        probe = MultiDeviceServer(probe_cfg, serve_cfg, devices=[d0])
        probe.warmup()
        probe.start(watch_checkpoints=False)
        try:
            # two passes, keep the MIN: the probe's noise is one-sided in
            # its damage — a cold reading just pads the crest's headroom,
            # but a hot one inflates base_rate past what the fleet can
            # absorb and charges the miss to the autoscaler
            reads = []
            for rep in range(2):
                prow = ScenarioRunner(
                    probe,
                    ScenarioSpec(name="probe", duration_s=2.0,
                                 base_rate=1200.0, sessions=sessions,
                                 seed=seed + 7 + rep),
                    slo_ms=slo_ms,
                ).run()
                reads.append(float(prow["throughput_rps"]))
        finally:
            probe.stop()
        capacity = max(min(reads), 20.0)
        # the probe reads SATURATED throughput (deep batches amortize
        # dispatch) and is itself noisy run-to-run; sustainable
        # interactive rate is lower than either reading. base =
        # capacity/5 keeps the 3x crest inside one replica's interactive
        # comfort even on an optimistic probe — the scale-up trigger is
        # the PREDICTIVE p99 headroom margin, not a queue backlog, so
        # the crest never needs to strain a replica for the second one
        # to be bought in time
        base_rate = round(capacity / 5.0, 1)
        print(
            f"[autoscale] calibrated: one replica ~{capacity:.0f} rps -> "
            f"base_rate={base_rate} (peak {3 * base_rate:.0f})",
            file=sys.stderr,
        )
    else:
        capacity = 0.0

    spec = ScenarioSpec(
        name="diurnal", duration_s=seconds, base_rate=base_rate,
        rate_profile="diurnal", peak_mult=3.0, sessions=sessions,
        # short sessions = realistic churn: new sessions keep arriving
        # through the crest, so a freshly activated replica picks up
        # load through least-loaded routing instead of idling behind
        # the incumbents' affinity
        session_mean_requests=8.0,
        seed=seed + 1,
    )
    arms = {}
    chip_seconds = {}
    horizon = 0.0
    trace = []

    for arm in ("autoscale", "static"):
        if arm == "autoscale":
            cfg = cfg0.replace(
                serve_autoscale=True, serve_devices=1,
                autoscale_min_replicas=1, autoscale_max_replicas=2,
                # predictive up (p99 past HALF the SLO budget on the ramp
                # buys the replica while every request is still inside the
                # SLO — waiting for a queue backlog makes the trigger a
                # timing lottery and the warmup window a miss window),
                # modest down-dwell (2 s of unbroken health): the
                # drain-requires-idle hold carries the real guard — a
                # drain is a migration wave and only fires once a
                # replica is truly quiet, i.e. in the post-scenario
                # tail, where it pays nothing and starts the
                # chip-second savings sooner
                autoscale_pressure_margin=0.5,
                autoscale_dwell_up=2, autoscale_dwell_down=8,
                autoscale_cooldown_s=1.0, autoscale_interval_s=0.25,
                autoscale_idle_age_s=0.5,
            ).validate()
            server = MultiDeviceServer(cfg, serve_cfg, devices=[d0])
        else:
            cfg = cfg0.replace(serve_devices=2).validate()
            server = MultiDeviceServer(cfg, serve_cfg, devices=[d0, d0])
        t0 = time.perf_counter()
        server.warmup()
        print(f"[autoscale:{arm}] warmup in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        server.start(watch_checkpoints=False)
        try:
            before = server.stats()
            server.degrade.reset_window()
            row = ScenarioRunner(
                server, spec, slo_ms=slo_ms, timeline=True
            ).run()
            if arm == "autoscale":
                # post-scenario idle tail: the drain decision needs
                # dwell_down healthy ticks (+ the stale-window horizon if
                # the tail produced no fresh samples) — the scale-DOWN
                # half of the elastic round trip
                deadline = time.monotonic() + 20.0
                while time.monotonic() < deadline:
                    st = server.autoscale.stats()
                    if st["autoscale_scale_downs"] >= 1:
                        break
                    time.sleep(0.1)
                # measured horizon: fleet start -> now, the window the
                # chip-second integral covers; the static fleet is
                # charged 2 replicas over the SAME horizon
                end = time.monotonic()
                chip_seconds[arm] = round(
                    server.autoscale.chip_seconds(until=end), 2
                )
                horizon = round(end - server.autoscale._t0, 2)
                trace = server.autoscale.replica_trace()
                auto_stats = server.autoscale.stats()
            after = server.stats()
        finally:
            server.stop()
        arms[arm] = {
            **row,
            "sessions_lost": after["sessions_lost"] - before["sessions_lost"],
            "sessions_migrated": after["sessions_migrated"]
            - before["sessions_migrated"],
            "shed": after["shed"] - before["shed"],
            "replicas_added": after.get("replicas_added", 0),
            "replicas_killed": after.get("replicas_killed", 0),
            "degrade_rung_ups": after.get("degrade_rung_ups", 0),
            "degrade_gated_holds": after.get("degrade_gated_holds", 0),
        }
        print(
            f"[autoscale:{arm}] slo={row['slo_attainment']:.3f} "
            f"p99={row.get('p99_latency_ms') and round(row['p99_latency_ms'], 1)}ms "
            f"errors={row['errors_total']} "
            f"lost={arms[arm]['sessions_lost']}",
            file=sys.stderr,
        )
    chip_seconds["static"] = round(2.0 * horizon, 2)
    report = {
        "metric": "serve_autoscale_diurnal",
        "unit": "comparison",
        "value": round(
            1.0 - chip_seconds["autoscale"] / max(chip_seconds["static"],
                                                  1e-9),
            4,
        ),  # fraction of chip-seconds the elastic fleet saved
        "slo_ms": slo_ms,
        "base_rate": base_rate,
        "peak_rate": round(3 * base_rate, 1),
        "capacity_rps_one_replica": round(capacity, 1),
        "duration_s": seconds,
        "sessions": sessions,
        "seed": seed,
        "scale_ups": auto_stats["autoscale_scale_ups"],
        "scale_downs": auto_stats["autoscale_scale_downs"],
        "autoscale_evaluations": auto_stats["autoscale_evaluations"],
        "replica_trace": trace,
        "chip_seconds": chip_seconds,
        "horizon_s": horizon,
        "shared_device": len(jax.local_devices()) < 2,
        "arms": arms,
        "core": cfg0.recurrent_core
        + (f"_c{cfg0.lru_chunk}" if cfg0.lru_chunk else ""),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        print(f"[autoscale] report -> {out_path}", file=sys.stderr)
    print(json.dumps(report))


def liveloop_main(
    core: str = "lstm",
    lru_chunk: int = 0,
    sessions: int = 8,
    seconds: float = 30.0,
    arrival_rate: float = 60.0,
    seed: int = 0,
    out_path: str = "",
    cfg_overrides: "Optional[dict]" = None,
    return_row: bool = False,
):
    """Live-loop learning bench: the full serve -> replay -> learn ->
    publish circle in one process (liveloop/). A two-replica fleet serves
    catch sessions; the TransitionTap feeds every served transition
    through the ingestion bridge into host replay; a LiveLoopTrainer runs
    continuous updates off that store in the main thread; and every
    save_interval crossing writes a checkpoint that the fleet's stock
    ckpt watcher hot-reloads mid-run — so the headline row certifies the
    loop actually closes: >= 1 reload of SELF-TRAINED params with
    params_version advancing, sessions_lost == 0.

    Traffic is Poisson-paced per session thread at a FIXED aggregate
    arrival rate; each session runs its own CatchHostEnv closed-loop and
    ships the terminal reward on the reset=True request (the liveloop
    client protocol — see liveloop/tap.py). The report is return per
    session over wall-clock: per-quarter mean episode return, first- vs
    second-half means, and per-session rows carrying the assigned
    exploration epsilon (the off-policy audit surface)."""
    import tempfile

    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.envs.catch import CatchHostEnv
    from r2d2_tpu.liveloop import LiveLoopPlane, LiveLoopTrainer
    from r2d2_tpu.serve import LocalClient, MultiDeviceServer, ServeConfig

    ckpt_dir = tempfile.mkdtemp(prefix="liveloop_bench_")
    overrides = dict(
        env_name="catch",
        action_dim=3,
        liveloop=True,
        checkpoint_dir=ckpt_dir,
        # cadences sized so several publish->reload cycles land inside
        # the window: learning starts after ~2s of traffic at the default
        # rate, and every 20 updates cuts a checkpoint for the watcher
        save_interval=20,
        learning_starts=128,
        buffer_capacity=4096,
        training_steps=1_000_000,  # wall clock, not step count, ends the run
        serve_spill=4 * sessions,
        **_core_overrides(core, lru_chunk),
    )
    # caller overrides (replay-scale mode re-runs this loop with the disk
    # tier + codec on) win over the literals above
    overrides.update(cfg_overrides or {})
    cfg = tiny_test().replace(**overrides).validate()
    serve_cfg = ServeConfig(
        buckets=(2, 4, 8),
        max_wait_ms=2.0,
        cache_capacity=max(16, sessions),
        poll_interval_s=0.25,  # tight watcher cadence: reloads land mid-run
        seed=seed,
    )
    trainer = LiveLoopTrainer(cfg)
    d0 = jax.local_devices()[0]
    server = MultiDeviceServer(
        cfg, serve_cfg, checkpoint_dir=ckpt_dir, devices=[d0, d0]
    )
    plane = LiveLoopPlane(cfg, server, trainer.replay, seed=seed)
    t0 = time.perf_counter()
    server.warmup()
    print(f"[liveloop] warmup in {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    server.start(watch_checkpoints=True)
    plane.start()
    version0 = server.stats()["params_version"]

    stop = threading.Event()
    rec_lock = threading.Lock()
    latencies: list = []  # submit -> action, seconds
    episodes: list = []  # (t_end_rel_s, session_idx, return, length)
    t0 = time.perf_counter()
    per_session_rate = max(arrival_rate / max(sessions, 1), 1e-6)

    def session_body(idx: int) -> None:
        # one live session: closed-loop catch, Poisson-paced requests.
        # After a terminal step the NEXT request carries reset=True, the
        # terminal reward, and the fresh episode's first frame — the tap
        # closes the episode off that one request.
        rng = np.random.default_rng(seed * 1009 + idx)
        env = CatchHostEnv(
            height=cfg.obs_shape[0], width=cfg.obs_shape[1],
            seed=seed * 1009 + idx,
        )
        client = LocalClient(server)
        sid = f"live-{idx}"
        obs, reward, reset = env.reset(), 0.0, True
        ep_ret, ep_len = 0.0, 0
        while not stop.is_set():
            t_req = time.perf_counter()
            try:
                res = client.act(sid, obs, reward=reward, reset=reset)
            except Exception:
                # shed/transient: abandon the episode, restart the stream
                obs, reward, reset = env.reset(), 0.0, True
                ep_ret, ep_len = 0.0, 0
                time.sleep(rng.exponential(1.0 / per_session_rate))
                continue
            with rec_lock:
                latencies.append(time.perf_counter() - t_req)
            reset = False
            obs, reward, done, _ = env.step(res.action)
            ep_ret += reward
            ep_len += 1
            if done:
                with rec_lock:
                    episodes.append(
                        (time.perf_counter() - t0, idx, ep_ret, ep_len)
                    )
                # terminal reward stays in `reward` for the next request
                obs, reset = env.reset(), True
                ep_ret, ep_len = 0.0, 0
            time.sleep(rng.exponential(1.0 / per_session_rate))

    threads = [
        threading.Thread(target=session_body, args=(i,),
                         name=f"live-session-{i}", daemon=True)
        for i in range(sessions)
    ]
    for t in threads:
        t.start()

    deadline = time.monotonic() + seconds
    updates = 0
    first_reload_s = None
    while time.monotonic() < deadline:
        plane.check()  # liveloop workers must be alive, not just present
        if trainer.can_train():
            updates += trainer.train(8, deadline=deadline)
        else:
            time.sleep(0.05)
        if first_reload_s is None and server.stats()["reloads"] > 0:
            first_reload_s = round(time.perf_counter() - t0, 2)

    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    wall = time.perf_counter() - t0
    plane.stop()  # final drains: queued records/blocks land in replay
    trainer.finish()
    loop_stats = plane.stats()
    learn_stats = trainer.stats()
    stats = server.stats()
    server.stop()

    lat_ms = np.sort(np.asarray(latencies, np.float64)) * 1e3
    n_q = 4
    timeline = []
    for q in range(n_q):
        lo, hi = seconds * q / n_q, seconds * (q + 1) / n_q
        rs = [r for (t, _, r, _) in episodes if lo <= t < hi]
        timeline.append({
            "window_s": [round(lo, 2), round(hi, 2)],
            "episodes": len(rs),
            "mean_return": round(float(np.mean(rs)), 4) if rs else None,
        })
    half1 = [r for (t, _, r, _) in episodes if t < seconds / 2]
    half2 = [r for (t, _, r, _) in episodes if t >= seconds / 2]
    by_session: dict = {}
    for (_, idx, r, _) in episodes:
        by_session.setdefault(idx, []).append(r)
    session_rows = [
        {
            "session": f"live-{i}",
            "episodes": len(rs),
            "mean_return": round(float(np.mean(rs)), 4),
            "epsilon": plane.assigner.epsilon_of(f"live-{i}"),
        }
        for i, rs in sorted(by_session.items())
    ]
    row = {
        "metric": "liveloop_return_per_session",
        # headline: mean episode return over the window's second half —
        # the policy the loop trained and hot-reloaded mid-run
        "value": round(float(np.mean(half2)), 4) if half2 else None,
        "unit": "return/episode",
        "vs_baseline": None,
        "first_half_mean_return": (
            round(float(np.mean(half1)), 4) if half1 else None
        ),
        "return_timeline": timeline,
        "episodes_total": len(episodes),
        "sessions": sessions,
        "per_session": session_rows,
        "arrival_rate_target": arrival_rate,
        "arrival_rate_achieved": round(len(latencies) / wall, 2),
        "duration_s": round(wall, 2),
        "seed": seed,
        "p50_latency_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p95_latency_ms": round(float(np.percentile(lat_ms, 95)), 3),
        "p99_latency_ms": round(float(np.percentile(lat_ms, 99)), 3),
        "learner_updates": updates,
        "learner_step": learn_stats["learner_step"],
        "reloads": stats["reloads"],
        "first_reload_s": first_reload_s,
        "params_version_start": version0,
        "params_version_final": stats["params_version"],
        "sessions_lost": stats["sessions_lost"],
        **{k: v for k, v in loop_stats.items() if k != "eps_ladder"},
        # {} unless the disk replay tier is on (replay-scale reruns)
        **getattr(trainer.replay, "disk_stats", dict)(),
        "core": cfg.recurrent_core
        + (f"_c{cfg.lru_chunk}" if cfg.lru_chunk else ""),
    }
    print(
        f"[liveloop] {len(episodes)} episodes / {len(latencies)} requests "
        f"in {wall:.1f}s; updates={updates} reloads={row['reloads']} "
        f"version {version0}->{row['params_version_final']} "
        f"return {row['first_half_mean_return']} -> {row['value']} "
        f"lost={row['sessions_lost']}",
        file=sys.stderr,
    )
    if row["reloads"] < 1 or row["params_version_final"] <= version0:
        raise SystemExit(
            "[liveloop] FAIL: no mid-run hot reload of self-trained params "
            f"(reloads={row['reloads']}, version {version0}->"
            f"{row['params_version_final']}) — the loop did not close"
        )
    if row["sessions_lost"]:
        raise SystemExit(
            f"[liveloop] FAIL: sessions_lost={row['sessions_lost']} != 0"
        )
    if out_path:
        with open(out_path, "w") as f:
            json.dump(row, f, indent=1)
        print(f"[liveloop] report -> {out_path}", file=sys.stderr)
    if return_row:
        return row
    print(json.dumps(row))


def podloop_main(
    hosts: int = 2,
    sessions: int = 8,
    seconds: float = 90.0,
    arrival_rate: float = 60.0,
    seed: int = 0,
    out_path: str = "",
):
    """Pod-loop bench: the live loop across REAL process boundaries
    (transport/podloop.py) — N serve-host processes feed one learner
    process over the block-stream transport; checkpoints broadcast back
    over the same sockets. This driver process only spawns the pod,
    generates closed-loop catch traffic against each host's TCP frontend
    (PolicyClient), and reads the children's stats jsonl.

    Mid-run SIGKILL drill: at ~40% of the window host h0 is SIGKILLed and
    relaunched with the SAME spool dir, host id, and serve port. The row
    certifies: the learner never stops training through the outage
    (learner_step strictly advances), the restarted host resumes its
    sequence from the on-disk spool (the learner's per-host high-water
    mark advances past its kill-time value), `duplicate_blocks == 0`
    end-to-end (the HELLO_ACK resume protocol de-duplicated the replayed
    tail), and `sessions_lost == 0` on every host. **Ingest lag** —
    serve-host spool time to trainable-in-replay time — is the headline
    first-class column.

    A CPU demonstration by construction: a chip belongs to one process,
    so the pod's processes are all started under JAX_PLATFORMS=cpu and
    the row says `platform: cpu` whatever this driver process runs on."""
    import signal as _signal
    import subprocess
    import tempfile

    from r2d2_tpu.envs.catch import CatchHostEnv
    from r2d2_tpu.serve import PolicyClient
    from r2d2_tpu.transport.podloop import podloop_config

    cfg = podloop_config(seed, checkpoint_dir="")  # driver-side env shapes
    root = tempfile.mkdtemp(prefix="podloop_bench_")
    spool_root = os.path.join(root, "spool")
    ckpt_dir = os.path.join(root, "ckpt")
    os.makedirs(spool_root, exist_ok=True)
    os.makedirs(ckpt_dir, exist_ok=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def _spawn(argv, logname):
        log = open(os.path.join(root, logname), "w")
        return subprocess.Popen(
            [sys.executable, "-m", "r2d2_tpu.transport.podloop"] + argv,
            stdout=subprocess.PIPE, stderr=log, env=env, text=True,
        ), log

    def _wait_ready(proc, timeout=180.0):
        import select as _select
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise SystemExit(
                    f"[podloop] FAIL: child exited rc={proc.returncode} "
                    "before ready"
                )
            r, _, _ = _select.select([proc.stdout], [], [], 0.5)
            if r:
                line = proc.stdout.readline()
                if not line:
                    continue
                try:
                    msg = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if msg.get("podloop_ready"):
                    return msg
        raise SystemExit("[podloop] FAIL: child not ready in time")

    def _last_stats(path, role=None):
        best = None
        try:
            with open(path) as f:
                for line in f:
                    try:
                        row = json.loads(line)
                    except json.JSONDecodeError:
                        continue  # torn final line from a SIGKILL
                    if role is None or row.get("role") == role:
                        best = row
        except OSError:
            pass
        return best or {}

    learner_stats_path = os.path.join(root, "learner.jsonl")
    learner, learner_log = _spawn(
        ["--role", "learner", "--ckpt-dir", ckpt_dir,
         "--stats", learner_stats_path, "--seed", str(seed)],
        "learner.log",
    )
    ingest_port = _wait_ready(learner)["ingest_port"]
    print(f"[podloop] learner up, ingest port {ingest_port}",
          file=sys.stderr)

    host_stats_path = [os.path.join(root, f"h{i}.jsonl") for i in range(hosts)]

    def _spawn_host(i, port=0):
        proc, log = _spawn(
            ["--role", "serve", "--host-id", f"h{i}",
             "--learner-port", str(ingest_port), "--port", str(port),
             "--spool-dir", spool_root, "--stats", host_stats_path[i],
             "--seed", str(seed + i)],
            f"h{i}.log" if port == 0 else f"h{i}_restarted.log",
        )
        return proc, log, _wait_ready(proc)["serve_port"]

    host_procs, host_logs, host_ports = [], [], []
    for i in range(hosts):
        proc, log, port = _spawn_host(i)
        host_procs.append(proc)
        host_logs.append(log)
        host_ports.append(port)
        print(f"[podloop] serve host h{i} up on port {port}",
              file=sys.stderr)

    stop = threading.Event()
    rec_lock = threading.Lock()
    latencies: list = []
    episodes: list = []  # (t_end_rel_s, session_idx, return, length)
    errors = [0]
    t0 = time.perf_counter()
    per_session_rate = max(arrival_rate / max(sessions, 1), 1e-6)

    def session_body(idx: int) -> None:
        # closed-loop catch against ONE host's TCP frontend; errors
        # (including the whole SIGKILL outage window) reset the episode
        # and keep offering — the client's own retries ride the restart
        rng = np.random.default_rng(seed * 1009 + idx)
        host_idx = idx % hosts
        env_ = CatchHostEnv(
            height=cfg.obs_shape[0], width=cfg.obs_shape[1],
            seed=seed * 1009 + idx,
        )
        client = PolicyClient("127.0.0.1", host_ports[host_idx],
                              timeout=5.0, retries=2, seed=idx)
        sid = f"pod-{idx}"
        obs, reward, reset = env_.reset(), 0.0, True
        ep_ret, ep_len = 0.0, 0
        while not stop.is_set():
            t_req = time.perf_counter()
            try:
                res = client.act(sid, obs, reward=reward, reset=reset)
            except Exception:
                with rec_lock:
                    errors[0] += 1
                obs, reward, reset = env_.reset(), 0.0, True
                ep_ret, ep_len = 0.0, 0
                stop.wait(min(rng.exponential(1.0 / per_session_rate), 0.5))
                continue
            with rec_lock:
                latencies.append(time.perf_counter() - t_req)
            reset = False
            obs, reward, done, _ = env_.step(res["action"])
            ep_ret += reward
            ep_len += 1
            if done:
                with rec_lock:
                    episodes.append(
                        (time.perf_counter() - t0, idx, ep_ret, ep_len)
                    )
                obs, reset = env_.reset(), True
                ep_ret, ep_len = 0.0, 0
            stop.wait(rng.exponential(1.0 / per_session_rate))

    threads = [
        threading.Thread(target=session_body, args=(i,),
                         name=f"pod-session-{i}", daemon=True)
        for i in range(sessions)
    ]
    for t in threads:
        t.start()

    # ---- SIGKILL drill on h0 at ~40% of the window
    kill_at = seconds * 0.4
    deadline = time.monotonic() + seconds
    time.sleep(max(kill_at - (time.perf_counter() - t0), 0.0))
    pre_kill = _last_stats(learner_stats_path)
    seq_at_kill = int(pre_kill.get("ingest_host_seq", {}).get("h0", 0))
    step_at_kill = int(pre_kill.get("learner_step", 0))
    host_procs[0].send_signal(_signal.SIGKILL)
    host_procs[0].wait(timeout=10.0)
    t_kill = round(time.perf_counter() - t0, 2)
    print(f"[podloop] SIGKILL h0 at {t_kill}s "
          f"(h0 seq {seq_at_kill}, learner step {step_at_kill})",
          file=sys.stderr)
    # relaunch with the SAME identity: host id, spool dir, serve port
    proc, log, port = _spawn_host(0, port=host_ports[0])
    host_procs[0], restart_log = proc, log
    assert port == host_ports[0]
    t_back = round(time.perf_counter() - t0, 2)
    print(f"[podloop] h0 back on port {port} at {t_back}s", file=sys.stderr)

    while time.monotonic() < deadline:
        if learner.poll() is not None:
            raise SystemExit(
                f"[podloop] FAIL: learner died rc={learner.returncode}"
            )
        time.sleep(0.5)

    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    wall = time.perf_counter() - t0
    learner_alive = learner.poll() is None

    # graceful drain: hosts first (their final flush pushes the spool
    # tail), then the learner
    for proc in host_procs:
        if proc.poll() is None:
            proc.send_signal(_signal.SIGTERM)
    for proc in host_procs:
        try:
            proc.wait(timeout=30.0)
        except subprocess.TimeoutExpired:
            proc.kill()
    learner.send_signal(_signal.SIGTERM)
    try:
        learner.wait(timeout=30.0)
    except subprocess.TimeoutExpired:
        learner.kill()
    for log in host_logs + [learner_log, restart_log]:
        log.close()

    lstats = _last_stats(learner_stats_path)
    hstats = [_last_stats(p) for p in host_stats_path]
    h0_final_seq = int(lstats.get("ingest_host_seq", {}).get("h0", 0))
    duplicate_blocks = int(lstats.get("ingest_duplicate_blocks", 0))
    sessions_lost = sum(int(h.get("sessions_lost", 0)) for h in hstats)
    reconnects_h0 = int(hstats[0].get("transport_reconnects", 0))

    half2 = [r for (t, _, r, _) in episodes if t >= seconds / 2]
    lat_ms = np.sort(np.asarray(latencies, np.float64)) * 1e3
    row = {
        "metric": "podloop_ingest_lag_p95_ms",
        # headline: serve-host spool time -> trainable-in-replay time,
        # measured by the learner per block, across the process boundary
        "value": lstats.get("ingest_lag_p95_ms"),
        "unit": "ms",
        "vs_baseline": None,
        # every pod process is pinned there (see env above)
        "platform": env["JAX_PLATFORMS"],
        "ingest_lag_p50_ms": lstats.get("ingest_lag_p50_ms"),
        "ingest_lag_max_ms": lstats.get("ingest_lag_max_ms"),
        "hosts": hosts,
        "sessions": sessions,
        "duration_s": round(wall, 2),
        "arrival_rate_target": arrival_rate,
        "agg_requests_per_s": round(len(latencies) / wall, 2),
        "request_errors": errors[0],
        "episodes_total": len(episodes),
        "return_per_session_2nd_half": (
            round(float(np.mean(half2)), 4) if half2 else None
        ),
        "p50_latency_ms": (
            round(float(np.percentile(lat_ms, 50)), 3) if len(lat_ms) else None
        ),
        "p95_latency_ms": (
            round(float(np.percentile(lat_ms, 95)), 3) if len(lat_ms) else None
        ),
        "learner_step_final": int(lstats.get("learner_step", 0)),
        "params_version_final": int(lstats.get("params_version", 0)),
        "ingest_blocks": int(lstats.get("ingest_blocks", 0)),
        # wire-cost accounting (PR 19): what the learner actually received
        # vs what those blocks cost raw, and the per-host publisher view
        "bytes_on_wire": int(lstats.get("ingest_bytes_on_wire", 0)),
        "bytes_pre_codec": int(lstats.get("ingest_bytes_decoded", 0)),
        "codec_ratio": lstats.get("ingest_codec_ratio", 0.0),
        "host_bytes_on_wire": [
            int(h.get("transport_bytes_on_wire", 0)) for h in hstats
        ],
        "host_codec_ratio": [
            h.get("transport_codec_ratio", 0.0) for h in hstats
        ],
        "ckpts_broadcast": int(lstats.get("ingest_ckpts_broadcast", 0)),
        "host_reloads": [int(h.get("reloads", 0)) for h in hstats],
        "sigkill_drill": {
            "killed_host": "h0",
            "t_kill_s": t_kill,
            "t_back_s": t_back,
            "h0_seq_at_kill": seq_at_kill,
            "h0_seq_final": h0_final_seq,
            "learner_step_at_kill": step_at_kill,
            "learner_uninterrupted": bool(learner_alive),
            "h0_reconnects_after_restart": reconnects_h0,
            "duplicate_blocks": duplicate_blocks,
            "sessions_lost": sessions_lost,
        },
        "seed": seed,
    }
    print(
        f"[podloop] {len(episodes)} episodes / {len(latencies)} requests "
        f"in {wall:.1f}s; learner step {row['learner_step_final']} "
        f"version {row['params_version_final']} "
        f"lag p95 {row['value']}ms; drill: h0 seq {seq_at_kill}->"
        f"{h0_final_seq} dupes={duplicate_blocks} lost={sessions_lost}",
        file=sys.stderr,
    )
    if not learner_alive:
        raise SystemExit(
            "[podloop] FAIL: learner did not run uninterrupted through "
            "the SIGKILL drill"
        )
    if row["learner_step_final"] <= step_at_kill:
        raise SystemExit(
            "[podloop] FAIL: learner made no progress after the kill "
            f"({step_at_kill} -> {row['learner_step_final']})"
        )
    if h0_final_seq <= seq_at_kill:
        raise SystemExit(
            "[podloop] FAIL: restarted host h0 never resumed its stream "
            f"(seq {seq_at_kill} -> {h0_final_seq})"
        )
    if duplicate_blocks:
        raise SystemExit(
            f"[podloop] FAIL: duplicate_blocks={duplicate_blocks} != 0 — "
            "the HELLO_ACK resume protocol leaked a replayed block"
        )
    if sessions_lost:
        raise SystemExit(
            f"[podloop] FAIL: sessions_lost={sessions_lost} != 0"
        )
    if row["params_version_final"] < 1 or row["ckpts_broadcast"] < 1:
        raise SystemExit(
            "[podloop] FAIL: no checkpoint ever broadcast back to the "
            "hosts — the pod loop did not close"
        )
    if sum(row["host_reloads"]) < 1:
        raise SystemExit(
            "[podloop] FAIL: no serve host ever installed a broadcast "
            "checkpoint (host_reloads all zero)"
        )
    if out_path:
        with open(out_path, "w") as f:
            json.dump(row, f, indent=1)
        print(f"[podloop] report -> {out_path}", file=sys.stderr)
    print(json.dumps(row))


def _rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    return 0.0


def replay_scale_main(
    scale: int = 10,
    sessions: int = 6,
    seconds: float = 25.0,
    arrival_rate: float = 60.0,
    seed: int = 0,
    out_path: str = "BENCH_r19.json",
):
    """Replay-at-production-scale bench (PR 19): the three-tier store —
    HBM staging / host slab / mmap disk segments — measured as one table
    of capacity, bytes/transition, and sample latency per tier, plus the
    two claims the tier has to certify:

    - **capacity x flat RAM**: a disk-backed store retains `scale`x the
      transitions of the host-only store while the host slab allocation
      (the RAM that scales with retention on the old plane) stays at the
      baseline size — the disk tier absorbs the growth, compressed by the
      delta-zlib block codec;
    - **the loop still closes**: the PR 12 liveloop bench re-runs on top
      of the scaled store (serve -> tap -> replay-with-demotions -> learn
      -> hot-reload) and must still hot-reload self-trained params with
      sessions_lost == 0 — demoted blocks stay sampleable mid-training.

    A resume row round-trips the populated tier through save_replay /
    restore_replay and fingerprints the restored store (tree mass +
    post-restore sample stream) against the original — the crash-recovery
    contract at scale."""
    import tempfile

    from r2d2_tpu.replay import codec as blockcodec
    from r2d2_tpu.replay.snapshot import (
        restore_replay, save_replay, snapshot_topology,
    )
    from r2d2_tpu.replay.tiered_store import TieredReplayBuffer
    from tests.test_replay_buffer import make_block, small_cfg

    host_cap = 16 * 12  # 16 host blocks of block_length 12
    disk_cap = (scale - 1) * host_cap
    disk_dir = tempfile.mkdtemp(prefix="replay_scale_disk_")

    base_kw = dict(buffer_capacity=host_cap, learning_starts=24,
                   replay_plane="tiered")
    cfg_host = small_cfg(**base_kw)
    cfg_disk = small_cfg(
        **base_kw, replay_disk_dir=disk_dir,
        replay_disk_capacity=disk_cap, block_codec="delta-zlib",
    )

    def fill(buf, cfg, blocks):
        for i in range(blocks):
            block, prios, ep = make_block(
                cfg, steps=12, start_step=13 * i, terminal=(i % 5 == 4),
                seed=seed + i,
            )
            buf.add_block(block, prios, ep)

    def slab_mb(buf):
        return sum(
            getattr(buf, f"{name}_store").nbytes
            for name in ("obs", "last_action", "last_reward", "action",
                         "n_step_reward", "gamma")
        ) / 2**20

    def sample_lat_ms(buf, draws=60):
        rng = np.random.default_rng(seed)
        ts = []
        for _ in range(draws):
            t0 = time.perf_counter()
            buf.sample_window_stack(rng, 2)
            ts.append((time.perf_counter() - t0) * 1e3)
        ts = np.sort(np.asarray(ts))
        return (round(float(np.percentile(ts, 50)), 3),
                round(float(np.percentile(ts, 95)), 3))

    total_blocks = scale * (host_cap // cfg_host.block_length)

    rss0 = _rss_mb()
    buf_host = TieredReplayBuffer(cfg_host)
    fill(buf_host, cfg_host, total_blocks)  # wraps: only host_cap retained
    rss_host = _rss_mb()
    host_p50, host_p95 = sample_lat_ms(buf_host)

    buf_disk = TieredReplayBuffer(cfg_disk)
    fill(buf_disk, cfg_disk, total_blocks)  # demotes: scale*host_cap live
    rss_disk = _rss_mb()
    disk_p50, disk_p95 = sample_lat_ms(buf_disk)
    dstats = buf_disk.disk_stats()

    retained_host = int(buf_host.occupied.sum()) * cfg_host.block_length
    retained_disk = int(buf_disk.occupied.sum()) * cfg_disk.block_length
    raw_bpt = slab_mb(buf_host) * 2**20 / host_cap
    disk_bpt_raw = dstats["disk_bytes_raw"] / max(
        dstats["disk_writes"] * cfg_disk.block_length, 1)
    disk_bpt_enc = dstats["disk_bytes_enc"] / max(
        dstats["disk_writes"] * cfg_disk.block_length, 1)

    # obs-plane codec ratio on catch-shaped frames (the acceptance gate's
    # >= 3x claim is about the obs plane, the field that dominates wire
    # and disk cost at production frame sizes)
    rng = np.random.default_rng(seed)
    obs = np.zeros((80, 5, 5, 1), np.uint8)
    for t in range(80):
        obs[t, t % 5, rng.integers(0, 5), 0] = 1
        obs[t, 4, rng.integers(0, 5), 0] = 1
    codec_ratio_obs = obs.nbytes / len(blockcodec.encode_field(obs))

    tier_table = [
        {
            "tier": "hbm_staging",
            "capacity_transitions": int(
                cfg_host.updates_per_dispatch * cfg_host.batch_size
                * cfg_host.seq_len
            ),
            "bytes_per_transition": round(raw_bpt, 1),
            "note": "transient double-buffered chunks; latency hidden "
                    "behind the learner dispatch (TransferTimer overlap)",
        },
        {
            "tier": "host_slab",
            "capacity_transitions": retained_host,
            "bytes_per_transition": round(raw_bpt, 1),
            "sample_p50_ms": host_p50,
            "sample_p95_ms": host_p95,
            "slab_mb": round(slab_mb(buf_host), 3),
        },
        {
            "tier": "disk_segments",
            "capacity_transitions": retained_disk - retained_host,
            "bytes_per_transition_raw": round(disk_bpt_raw, 1),
            "bytes_per_transition": round(disk_bpt_enc, 1),
            "sample_p50_ms": disk_p50,
            "sample_p95_ms": disk_p95,
            "slab_mb": round(slab_mb(buf_disk), 3),
            "demotions": dstats["disk_demotions"],
            "evictions": dstats["disk_evictions"],
        },
    ]

    # ---- resume-from-disk row: snapshot the populated tier, restore into
    # a fresh store, fingerprint tree mass + the post-restore sample stream
    snap_path = os.path.join(disk_dir, "scale_snapshot.npz")
    t0 = time.perf_counter()
    save_replay(buf_disk, snap_path,
                topology=snapshot_topology(buf_disk, tp=1))
    save_s = time.perf_counter() - t0
    buf_resumed = TieredReplayBuffer(
        cfg_disk.replace(replay_disk_dir=tempfile.mkdtemp(
            prefix="replay_scale_resume_"))
    )
    t0 = time.perf_counter()
    restore_replay(buf_resumed, snap_path)
    restore_s = time.perf_counter() - t0
    fp_equal = bool(
        np.isclose(buf_resumed.tree.total, buf_disk.tree.total)
        and np.array_equal(buf_resumed.occupied, buf_disk.occupied)
    )
    if fp_equal:
        rng_a, rng_b = (np.random.default_rng(seed + 7) for _ in range(2))
        for _ in range(4):
            sa = buf_disk.sample_window_stack(rng_a, 2)
            sb = buf_resumed.sample_window_stack(rng_b, 2)
            fp_equal = fp_equal and np.array_equal(sa.obs, sb.obs) \
                and np.array_equal(sa.idxes, sb.idxes)
    resume_row = {
        "snapshot_save_s": round(save_s, 3),
        "snapshot_restore_s": round(restore_s, 3),
        "fingerprint_equal": fp_equal,
        "disk_records_snapshotted": int(buf_disk.occupied[
            cfg_disk.num_blocks:].sum()),
    }
    del buf_host, buf_disk, buf_resumed

    # ---- the PR 12 liveloop, re-run on the scaled store: 10x retention,
    # demotions live under real traffic, loop must still close. The host
    # slab is sized well under the traffic the window produces so the
    # demotion path runs DURING training, not just in the fill above.
    live_disk_dir = tempfile.mkdtemp(prefix="replay_scale_live_")
    live_cap = 512
    rss_live0 = _rss_mb()
    live_row = liveloop_main(
        sessions=sessions, seconds=seconds, arrival_rate=arrival_rate,
        seed=seed, return_row=True,
        cfg_overrides=dict(
            replay_plane="tiered",
            buffer_capacity=live_cap,
            replay_disk_dir=live_disk_dir,
            replay_disk_capacity=(scale - 1) * live_cap,
            block_codec="delta-zlib",
        ),
    )
    rss_live1 = _rss_mb()

    row = {
        "metric": "replay_scale_capacity_ratio",
        # headline: live retained transitions vs the host-only store's, at
        # an unchanged host slab allocation
        "value": round(retained_disk / max(retained_host, 1), 2),
        "unit": "x",
        "vs_baseline": None,
        "scale_target": scale,
        "tier_table": tier_table,
        "codec_ratio_obs": round(codec_ratio_obs, 2),
        "codec": "delta-zlib",
        "rss_mb_baseline_fill": round(rss_host - rss0, 1),
        "rss_mb_scaled_fill": round(rss_disk - rss_host, 1),
        "rss_mb_liveloop_delta": round(rss_live1 - rss_live0, 1),
        "resume_from_disk": resume_row,
        "liveloop_at_scale": {
            k: live_row.get(k)
            for k in ("value", "first_half_mean_return", "episodes_total",
                      "reloads", "params_version_final", "sessions_lost",
                      "learner_updates", "disk_demotions", "disk_evictions",
                      "disk_occupied", "disk_codec_ratio", "duration_s")
        },
        "seed": seed,
    }
    print(
        f"[replay-scale] capacity x{row['value']} at slab "
        f"{tier_table[1]['slab_mb']}MB; disk bytes/transition "
        f"{disk_bpt_raw:.1f} raw -> {disk_bpt_enc:.1f} codec "
        f"(obs-plane x{codec_ratio_obs:.1f}); sample p50 "
        f"{host_p50}ms host / {disk_p50}ms mixed; resume "
        f"fingerprint_equal={fp_equal}; liveloop lost="
        f"{row['liveloop_at_scale']['sessions_lost']}",
        file=sys.stderr,
    )
    if row["value"] < scale * 0.95:
        raise SystemExit(
            f"[replay-scale] FAIL: capacity ratio {row['value']} < {scale}"
        )
    if codec_ratio_obs < 3.0:
        raise SystemExit(
            f"[replay-scale] FAIL: obs codec ratio {codec_ratio_obs:.2f} "
            "< 3.0 on catch-shaped frames"
        )
    if not fp_equal:
        raise SystemExit(
            "[replay-scale] FAIL: resume-from-disk fingerprint mismatch"
        )
    if row["liveloop_at_scale"]["sessions_lost"]:
        raise SystemExit(
            "[replay-scale] FAIL: sessions_lost != 0 on the scaled store"
        )
    if not row["liveloop_at_scale"]["disk_demotions"]:
        raise SystemExit(
            "[replay-scale] FAIL: the liveloop window produced no "
            "demotions — the claim 'demoted blocks stay sampleable "
            "mid-training' went unexercised (raise seconds/rate or "
            "shrink the host slab)"
        )
    if out_path:
        with open(out_path, "w") as f:
            json.dump(row, f, indent=1)
        print(f"[replay-scale] report -> {out_path}", file=sys.stderr)
    print(json.dumps(row))


def serve_main(
    core: str = "lstm",
    lru_chunk: int = 0,
    sessions: int = 0,
    seconds: float = 30.0,
    precision: str = "bf16",
    arrival_rate: float = 200.0,
    slo_ms: float = 50.0,
    devices: int = 1,
):
    """Serving-plane load test driver. Under --precision bf16/both an fp32
    reference arm runs first, so the headline row carries `vs_fp32` on
    requests/s measured at the identical session load; `both` also
    attaches the fp32 arm's numbers. Reports sustained requests/s plus
    p50/p95/p99 request latency (submit -> action), SLO attainment at
    --slo-ms, batch occupancy, reload count, session-tier spill/promote
    traffic, and the carry-cache precision footprint.

    The default load is OPEN-LOOP (--arrival-rate > 0, Poisson arrivals,
    sessions ≫ cache capacity — see _serve_load); --arrival-rate 0
    restores the closed-loop session-thread arm. `sessions` 0 = auto:
    256 open-loop (8x the derived cache capacity), 32 closed-loop.

    No baseline row exists yet for serving — vs_baseline is null until a
    BENCH_*.json round records the first trajectory point.

    --precision both runs a THIRD arm, serve_int8: the bf16 serve config
    with serve_quantization="int8" (weight-only per-channel int8 on the
    encoder/head kernels, ops/quantize.py). Its sub-row carries vs_fp32
    on requests/s plus `q_drift_vs_fp32` — the bounded-parity drift
    column, measured by a deterministic recurrent probe (_int8_q_drift)
    rather than inferred from the load arms' divergent action streams."""
    sessions = sessions or (256 if arrival_rate > 0 else 32)
    head_arm = "bf16" if precision in ("bf16", "both") else "fp32"
    if head_arm == "fp32":
        arm_names = ["fp32"]
    elif precision == "both":
        arm_names = ["fp32", "bf16", "int8"]
    else:
        arm_names = ["fp32", "bf16"]
    arms = {}
    for arm in arm_names:
        cfg = _system_cfg(
            core=core, lru_chunk=lru_chunk,
            precision="bf16" if arm == "int8" else arm,
        )
        if arm == "int8":
            cfg = cfg.replace(serve_quantization="int8")
        arms[arm] = _serve_load(cfg, sessions, seconds, label=arm,
                                arrival_rate=arrival_rate, slo_ms=slo_ms,
                                devices=devices)
    head = arms[head_arm]
    vs_fp32 = head["value"] / arms["fp32"]["value"]
    if head_arm != "fp32":
        print(
            f"[precision] serve bf16 {head['value']:.0f} vs fp32 "
            f"{arms['fp32']['value']:.0f} requests/s = {vs_fp32:.2f}x "
            f"(p50 {head['p50_latency_ms']:.2f} vs "
            f"{arms['fp32']['p50_latency_ms']:.2f} ms)",
            file=sys.stderr,
        )
    row = {
        "metric": "serve_requests_per_sec",
        **head,
        "unit": "requests/s",
        "vs_baseline": None,
        "vs_fp32": round(vs_fp32, 3),
        "sessions": sessions,
        "core": cfg.recurrent_core
        + (f"_c{cfg.lru_chunk}" if cfg.lru_chunk else ""),
        "precision": head_arm,
    }
    if precision == "both":
        row["fp32"] = arms["fp32"]
    if "int8" in arms:
        drift = _int8_q_drift(
            _system_cfg(core=core, lru_chunk=lru_chunk, precision="bf16")
        )
        print(
            f"[serve_int8] {arms['int8']['value']:.0f} requests/s "
            f"({arms['int8']['value'] / arms['fp32']['value']:.2f}x fp32), "
            f"q drift {drift:.2e} of fp32 Q scale",
            file=sys.stderr,
        )
        row["serve_int8"] = {
            **arms["int8"],
            "vs_fp32": round(arms["int8"]["value"] / arms["fp32"]["value"], 3),
            "q_drift_vs_fp32": round(drift, 6),
        }
    print(json.dumps(row))


def _rate_window(server, sessions: int, rate: float, seconds: float,
                 slo_ms: float, seed: int, seen: set) -> dict:
    """One open-loop Poisson window at a FIXED arrival rate against an
    ALREADY-RUNNING server — the rate search's unit probe. Unlike
    _serve_load the server (compiled buckets, carry cache, session
    population) persists across windows, so each probe costs only its own
    wall-clock; `seen` carries session novelty across windows so only the
    first window pays the new-session reset wave. The window ends with a
    bounded drain wait, so an overloaded probe's queue can't leak latency
    into the NEXT probe's numbers.

    Returns one trace row: offered rate, measured requests/s, p50/p99,
    and slo_attainment where a rejected, failed, or never-resolved
    request is a miss — not an absent sample."""
    from r2d2_tpu.serve import QueueFullError

    rng = np.random.default_rng(seed)
    records: list = []
    submitted = [0]
    session_obs: dict = {}
    t0 = time.perf_counter()
    next_t = t0
    deadline = t0 + seconds
    while True:
        next_t += rng.exponential(1.0 / rate)
        if next_t >= deadline:
            break
        now = time.perf_counter()
        if next_t > now:
            time.sleep(next_t - now)
        i = int(rng.integers(0, sessions))
        obs = session_obs.get(i)
        if obs is None:
            obs = rng.integers(0, 255, server.cfg.obs_shape, dtype=np.uint8)
            session_obs[i] = obs
        sid = f"rate-{i}"
        reset = sid not in seen
        seen.add(sid)
        t_sub = time.perf_counter()
        submitted[0] += 1
        fut = server.submit(sid, obs, reward=0.0, reset=reset)

        def _done(f, t_sub=t_sub):
            exc = f.exception()
            if exc is None:
                records.append((t_sub - t0, time.perf_counter() - t_sub, None))
            elif isinstance(exc, QueueFullError):
                records.append((t_sub - t0, None, "rejected"))
            else:
                records.append((t_sub - t0, None, "transport"))

        fut.add_done_callback(_done)
    drain_deadline = time.perf_counter() + max(5.0, seconds)
    while len(records) < submitted[0] and time.perf_counter() < drain_deadline:
        time.sleep(0.05)
    snapshot = list(records)  # late callbacks append past this point
    warmup_s = min(1.0, 0.2 * seconds)
    measured = [r for r in snapshot if r[0] >= warmup_s]
    unresolved = max(submitted[0] - len(snapshot), 0)
    ok = np.sort(np.asarray(
        [lat for _, lat, _ in measured if lat is not None]))
    offered = len(measured) + unresolved
    attained = int(np.count_nonzero(ok <= slo_ms / 1e3)) if ok.size else 0
    return {
        "rate": round(rate, 2),
        "requests_per_sec": round(ok.size / max(seconds - warmup_s, 1e-9), 1),
        "p50_latency_ms": round(float(np.percentile(ok, 50) * 1e3), 2)
        if ok.size else None,
        "p99_latency_ms": round(float(np.percentile(ok, 99) * 1e3), 2)
        if ok.size else None,
        "slo_attainment": round(attained / max(offered, 1), 4),
        "errors": sum(1 for _, _, e in measured if e is not None),
        "unresolved": unresolved,
    }


def _search_max_rate(window, start_rate: float, slo_target: float,
                     max_rate: float = 4096.0, bisect_steps: int = 4):
    """Double-then-bisect search for the highest arrival rate whose
    window still attains the SLO target. Doubling finds the bracket (the
    first failing rate), bisection tightens it; the reported
    max_rate_at_slo is always the highest rate that actually PASSED a
    window, never an interpolation. If even start_rate misses, halve
    down to 1 req/s before giving up at 0."""
    trace = []
    rate = start_rate
    row = window(rate)
    trace.append(row)
    while row["slo_attainment"] < slo_target and rate > 1.0:
        rate /= 2.0
        row = window(rate)
        trace.append(row)
    if row["slo_attainment"] < slo_target:
        return 0.0, trace
    lo, hi = rate, None
    while hi is None and rate < max_rate:
        rate *= 2.0
        row = window(rate)
        trace.append(row)
        if row["slo_attainment"] >= slo_target:
            lo = rate
        else:
            hi = rate
    if hi is None:
        hi = rate * 2.0
    for _ in range(bisect_steps):
        if hi - lo <= max(0.05 * lo, 2.0):
            break
        mid = (lo + hi) / 2.0
        row = window(mid)
        trace.append(row)
        if row["slo_attainment"] >= slo_target:
            lo = mid
        else:
            hi = mid
    return lo, trace


def _pipeline_parity_probe(core: str, lru_chunk: int) -> bool:
    """Bitwise pipelined-vs-serial action parity, in-process: one
    deterministic request stream (recurring sessions, resets, identical
    batch composition via direct batcher drives) through a serial server
    (serve_pipeline=False, _run_batch) and through a pipelined server
    hand-driven at depth 2 (_stage_and_dispatch now, _complete two
    batches later — the started pipeline's exact overlap, made
    deterministic). True iff every action and q row matches bit-for-bit.
    The full matrix (bf16, mixed-task buckets, mid-pipeline reload) lives
    in tests/test_serve_pipeline.py; this probe pins the benched build."""
    from collections import deque

    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.serve import PolicyServer, ServeConfig

    cfg = tiny_test().replace(**_core_overrides(core, lru_chunk)).validate()
    serve_cfg = ServeConfig(buckets=(2, 4, 8), max_wait_ms=3.0,
                            cache_capacity=64, epsilon=0.3)
    stream_rng = np.random.default_rng(77)
    sids = [f"parity-{i}" for i in range(6)]
    batches = []
    for b in range(12):
        n = 1 + (b % 4)
        picks = stream_rng.choice(len(sids), size=n, replace=False)
        batches.append([
            (sids[int(i)],
             stream_rng.integers(0, 255, cfg.obs_shape, dtype=np.uint8),
             float(stream_rng.standard_normal()),
             bool(stream_rng.integers(0, 4) == 0))
            for i in picks
        ])

    def run(pipelined: bool):
        srv = PolicyServer(cfg.replace(serve_pipeline=pipelined), serve_cfg)
        srv.warmup()
        futs, pending = [], deque()
        for rows in batches:
            for sid, obs, rew, rs in rows:
                futs.append(srv.submit(sid, obs, reward=rew, reset=rs))
            batch = srv.batcher.next_batch(timeout=1.0)
            if pipelined:
                if len(pending) == 2:
                    srv._complete(pending.popleft())
                pending.append(srv._stage_and_dispatch(batch))
            else:
                srv._run_batch(batch)
        while pending:
            srv._complete(pending.popleft())
        out = []
        for f in futs:
            res = f.result(timeout=5.0)
            out.append((res.action, np.asarray(res.q)))
        srv.stop()
        return out

    serial, pipe = run(False), run(True)
    return len(serial) == len(pipe) and all(
        a == b and np.array_equal(qa, qb)
        for (a, qa), (b, qb) in zip(serial, pipe)
    )


def serve_rate_search_main(
    core: str = "lstm",
    lru_chunk: int = 0,
    sessions: int = 64,
    seconds: float = 5.0,
    slo_ms: float = 50.0,
    slo_target: float = 0.99,
    start_rate: float = 32.0,
    out_path: str = "",
):
    """The serving plane's capacity headline: the maximum sustained
    Poisson arrival rate at which SLO attainment stays >= --slo-target,
    found by doubling then bisection and A/B'd between the serial serve
    path (serve_pipeline=False) and the depth-2 staged pipeline (the
    default). ONE server per arm is built, warmed, and REUSED across
    every rate window — a fresh server per probe would re-trace 5 buckets
    (tens of seconds each on CPU) and drown the measurement in compile
    noise.

    Alongside the A/B: an in-process bitwise action-parity probe (the
    pipeline must be a scheduling change, not a numerics change) and a
    two-replica replica-kill scenario cell run with the pipeline ON,
    whose sessions_lost must be 0 — kill-triggered migration has to drain
    mid-pipeline batches without dropping carries. --serve-out writes the
    whole report (the BENCH_r15.json shape)."""
    from r2d2_tpu.serve import (
        MultiDeviceServer,
        PolicyServer,
        ScenarioRunner,
        ServeConfig,
        builtin_scenarios,
    )

    base_cfg = _system_cfg(core=core, lru_chunk=lru_chunk, precision="fp32")
    base_cfg = base_cfg.replace(serve_spill=4 * sessions).validate()
    serve_cfg = ServeConfig(
        buckets=(2, 4, 8, 16, 32),
        max_wait_ms=2.0,
        cache_capacity=max(64, sessions),
        poll_interval_s=0.5,
    )
    arms = {}
    for arm, pipelined in (("serial", False), ("pipelined", True)):
        cfg = base_cfg.replace(serve_pipeline=pipelined).validate()
        server = PolicyServer(cfg, serve_cfg)
        t0 = time.perf_counter()
        server.warmup()
        print(
            f"[rate-search:{arm}] warmup in {time.perf_counter() - t0:.1f}s",
            file=sys.stderr,
        )
        server.start()
        try:
            seen: set = set()
            widx = [0]

            def window(rate, server=server, seen=seen, widx=widx, arm=arm):
                widx[0] += 1
                row = _rate_window(server, sessions, rate, seconds, slo_ms,
                                   seed=1000 + widx[0], seen=seen)
                print(
                    f"[rate-search:{arm}] rate={rate:.0f} "
                    f"slo={row['slo_attainment']:.3f} "
                    f"p99={row['p99_latency_ms']}ms "
                    f"rps={row['requests_per_sec']}",
                    file=sys.stderr,
                )
                return row

            max_rate, trace = _search_max_rate(window, start_rate, slo_target)
            server.check()
            stats = server.stats()
        finally:
            server.stop()
        arms[arm] = {
            "max_rate_at_slo": round(max_rate, 2),
            "windows": trace,
            "completed_batches": stats["completed_batches"],
            "metrics_skipped": stats["metrics_skipped"],
            "mean_batch_occupancy": round(stats["mean_batch_occupancy"], 2),
            "serve_pipeline": pipelined,
        }
    speedup = arms["pipelined"]["max_rate_at_slo"] / max(
        arms["serial"]["max_rate_at_slo"], 1e-9
    )
    print(
        f"[rate-search] pipelined {arms['pipelined']['max_rate_at_slo']:.0f} "
        f"vs serial {arms['serial']['max_rate_at_slo']:.0f} req/s at SLO "
        f"= {speedup:.2f}x",
        file=sys.stderr,
    )
    parity = _pipeline_parity_probe(core, lru_chunk)
    print(f"[rate-search] bitwise action parity: {parity}", file=sys.stderr)
    # kill cell: pipeline ON, two replicas, mid-scenario replica kill —
    # every routed session must come out the other side (migration drains
    # the victim's in-flight pipeline records before carries move)
    d0 = jax.local_devices()[0]
    fleet = MultiDeviceServer(base_cfg, serve_cfg, devices=[d0, d0])
    t0 = time.perf_counter()
    fleet.warmup()
    print(f"[rate-search:kill] warmup in {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    fleet.start(watch_checkpoints=False)
    try:
        spec = next(
            s for s in builtin_scenarios(
                base_rate=start_rate, duration_s=max(seconds, 4.0),
                sessions=sessions, seed=0,
            )
            if s.name == "replica_kill"
        )
        before = fleet.stats()
        cell = ScenarioRunner(fleet, spec, slo_ms=slo_ms).run()
        after = fleet.stats()
    finally:
        fleet.stop()
    kill_cell = {
        **cell,
        "sessions_lost": after["sessions_lost"] - before["sessions_lost"],
        "sessions_migrated": after["sessions_migrated"]
        - before["sessions_migrated"],
    }
    print(
        f"[rate-search:kill] lost={kill_cell['sessions_lost']} "
        f"migrated={kill_cell['sessions_migrated']} "
        f"kills={kill_cell.get('replica_kills')}",
        file=sys.stderr,
    )
    row = {
        "metric": "serve_max_rate_at_slo",
        "value": arms["pipelined"]["max_rate_at_slo"],
        "unit": "requests/s",
        "vs_baseline": None,
        "vs_serial": round(speedup, 3),
        "slo_ms": slo_ms,
        "slo_target": slo_target,
        "window_seconds": seconds,
        "sessions": sessions,
        "bitwise_action_parity": bool(parity),
        "arms": arms,
        "replica_kill": kill_cell,
        "core": base_cfg.recurrent_core
        + (f"_c{base_cfg.lru_chunk}" if base_cfg.lru_chunk else ""),
        "precision": "fp32",
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(row, f, indent=1)
        print(f"[rate-search] report -> {out_path}", file=sys.stderr)
    print(json.dumps(row))


def long_context_main(core: str = "lstm", lru_chunk: int = 0,
                      precision: str = "bf16"):
    """Stretch configuration (BASELINE.json config 5): seq_len = 64 burn-in
    + 512 learning + 5 forward = 581 per sequence — at batch 32, ~3.4x the
    frame volume per update of the reference shape (32 x 581 vs 64 x 85).
    Same fused K-update pipeline over HBM-resident replay; remat-chunked
    scan handles the long recurrence (config long_context preset,
    SURVEY.md section 5.7).

    Frames count 1:1 (Craftax/NetHack-class envs have no frameskip), and
    vs_baseline is against the BASELINE.json >=100k env-frames/s/chip
    north star — the reference cannot run this sequence shape at all."""
    from r2d2_tpu.config import long_context

    cfg = long_context().replace(
        batch_size=32,  # 32 x 581 frames/update fits HBM alongside the store
        **_precision_overrides("bf16" if precision == "both" else precision),
        buffer_capacity=102_400,  # 200 slots x 512 ~= 0.8 GB obs store
        # pin the benched shapes to the config-5 spec (84x84 Nature/512,
        # seq 581) regardless of what game/geometry the preset's DEFAULT
        # currently targets — the bench row must stay comparable across
        # rounds even as the preset's default task moves with the
        # learning-evidence frontier
        obs_shape=(84, 84, 1),
        encoder="nature",
        hidden_dim=512,
        burn_in_steps=64,
        learning_steps=512,
        forward_steps=5,
        block_length=1024,
        max_episode_steps=984,
        # the round-5 preset re-target also moved the preset's net/lr
        # defaults (lru core, cosine lr); the bench row keeps the
        # rounds-1..4 workload definition (constant lr; core from --core)
        lr_schedule="constant",
        **_core_overrides(core, lru_chunk),
    )
    main(
        cfg,
        K=4,
        metric="long_context_learner_env_frames_per_sec_per_chip",
        frame_multiplier=1,
        baseline=100_000.0,
    )


def multitask_main(
    updates: int = 1500,
    collect_per_update: int = 4,
    eval_episodes: int = 16,
    eval_horizon: int = 48,
    seed: int = 0,
    out_path: str = "BENCH_r13.json",
) -> dict:
    """Multi-task plane acceptance matrix (multitask/MultiTaskTrainer):
    ONE task-conditioned learner over the grown env family, then a
    PER-TASK trained-vs-seeded-random return comparison plus collection
    frames/sec. The bar is per-task — every task must beat its own random
    baseline; an average would let one dense-reward task mask a dead one.

    CPU-budget sizing: tiny_test geometry, a small keydoor variant
    (keydoor:4:2 — length-4 corridor, 2 colors) so the walk-right+open
    policy is reachable in a few hundred updates without an accelerator.
    """
    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.multitask import MultiTaskTrainer
    from r2d2_tpu.multitask.trainer import rollout_returns

    tasks = ["keydoor:4:2", "drift", "banditgrid", "catch"]
    cfg = tiny_test().replace(
        seed=seed,
        num_actors=16,          # 4 per task
        batch_size=16,
        buffer_capacity=5120,
        learning_starts=256,
        training_steps=updates,
        target_net_update_interval=40,
        lr=1e-3,                # tiny envs + tiny net: converge in minutes on CPU
    )
    trainer = MultiTaskTrainer(cfg, tasks)
    t0 = time.time()
    trainer.warmup()
    trainer.train(updates, collect_steps_per_update=collect_per_update)
    wall = time.time() - t0

    params, _ = trainer.param_store.latest()
    rows = []
    for spec in trainer.specs:
        ev_seed = 10_000 + 17 * spec.task_id  # seeded: same envs/noise both arms
        trained = rollout_returns(
            trainer.cfg, trainer.net, params, spec, episodes=eval_episodes,
            horizon=eval_horizon, seed=ev_seed, policy="greedy",
        )
        rand = rollout_returns(
            trainer.cfg, None, None, spec, episodes=eval_episodes,
            horizon=eval_horizon, seed=ev_seed, policy="random",
        )
        frames = trainer.replays[spec.task_id].env_steps
        rows.append({
            "task": spec.task_id,
            "env": spec.env_name,
            "trained_return": float(np.mean(trained)),
            "random_return": float(np.mean(rand)),
            "beats_random": bool(np.mean(trained) > np.mean(rand)),
            "frames": int(frames),
            "frames_per_sec": float(frames / wall),
        })
    report = {
        "metric": "multitask_matrix",
        "updates": updates,
        "eval_episodes": eval_episodes,
        "eval_horizon": eval_horizon,
        "wall_seconds": wall,
        "all_beat_random": bool(all(r["beats_random"] for r in rows)),
        "tasks": rows,
    }
    print(json.dumps(report))
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(report, fh, indent=2)
    return report


if __name__ == "__main__":
    import argparse

    # Persistent XLA cache (utils/compilation_cache.py holds the one
    # directory rule); JAX_ENABLE_COMPILATION_CACHE=0 measures a true
    # cold compile.
    from r2d2_tpu.utils.compilation_cache import (
        enable_compilation_cache,
        log_compile_cache_stats,
    )

    p = argparse.ArgumentParser(description="r2d2_tpu benchmarks")
    p.add_argument(
        "--mode", default="learner",
        choices=["learner", "system", "fused", "long_context", "serve",
                 "recovery", "scenarios", "liveloop",
                 "multitask", "autoscale", "podloop", "replay-scale"],
        help="learner: fused-update throughput on synthetic replay (the "
             "driver's default metric). system: concurrent on-device "
             "collection + learning via threads. fused: the same full "
             "system as ONE megastep dispatch (megastep.py). long_context: "
             "learner throughput on the seq-581 stretch preset. serve: "
             "serving-plane load test (r2d2_tpu/serve) — requests/s and "
             "latency percentiles under concurrent stateful sessions with "
             "a mid-window checkpoint hot-reload. recovery: preempt a run "
             "with an injected SIGTERM and measure resume-to-first-update "
             "wall time (utils/faults.py). "
             "scenarios: scenario x degradation-rung readiness matrix — "
             "every built-in traffic/chaos scenario (serve/scenarios.py) "
             "against every rung of the graceful-degradation ladder "
             "(serve/degrade.py) on a two-replica fleet, reporting p99, "
             "SLO attainment, error breakdown, q_drift_vs_fp32 and "
             "sessions_lost per cell. liveloop: the closed learning loop "
             "(liveloop/) — served catch traffic feeds replay through the "
             "transition tap, a continuous learner trains off it, and its "
             "checkpoints hot-reload the fleet mid-run; reports return "
             "per session over wall-clock at a fixed arrival rate. "
             "multitask: one task-conditioned learner over the pure-JAX "
             "env family (multitask/); per-task trained-vs-random return "
             "matrix + frames/sec, written to BENCH_r13.json. "
             "autoscale: the elastic fleet (serve/autoscale.py) vs a "
             "peak-sized static fleet on the diurnal scenario — SLO "
             "attainment, sessions_lost through one scale-up and one "
             "scale-down, replica-count trace, and chip-seconds, written "
             "to BENCH_r17.json. "
             "podloop: the live loop across real process boundaries "
             "(transport/) — N serve-host processes stream blocks to one "
             "learner process over the fault-tolerant block-stream "
             "transport, checkpoints broadcast back over the same "
             "sockets, with a mid-run SIGKILL-one-host drill; reports "
             "aggregate requests/s, return per session, and ingest lag, "
             "written to BENCH_r18.json. "
             "replay-scale: the three-tier replay store (HBM staging / "
             "host slab / mmap disk segments with the delta-zlib block "
             "codec) — per-tier capacity, bytes/transition, and sample "
             "latency, a resume-from-disk fingerprint row, and the PR 12 "
             "liveloop re-run at N-times retention on a flat host slab, "
             "written to BENCH_r19.json.",
    )
    p.add_argument(
        "--mt-updates", type=int, default=600,
        help="multitask mode: learner updates after warmup",
    )
    p.add_argument(
        "--mt-eval-episodes", type=int, default=16,
        help="multitask mode: eval episodes per task per arm",
    )
    p.add_argument(
        "--mt-out", default="BENCH_r13.json",
        help="multitask mode: report JSON path ('' to skip the file)",
    )
    p.add_argument(
        "--collect-every", type=int, default=6,
        help="fused mode: fold a collection chunk into every Nth dispatch",
    )
    p.add_argument(
        "--core", default="lstm", choices=["lstm", "lru"],
        help="recurrent core for the benched network (learner/system/fused "
             "modes). lru + --lru-chunk is the time-parallel MXU core",
    )
    p.add_argument(
        "--lru-chunk", type=int, default=0,
        help="LRU unroll formulation: 0 = associative scan, N > 0 = "
             "chunked triangular matmuls on the MXU (requires --core lru)",
    )
    p.add_argument(
        "--precision", default=None, choices=["fp32", "bf16", "both"],
        help="mixed-precision arm (config.precision). fp32: full float32 "
             "everywhere — the speedup denominator. bf16: bf16 matmuls, "
             "fp32 master params + fp32 loss/target/priority islands, "
             "bf16 recurrent-state storage in replay and the serve cache. "
             "both: run fp32 then bf16 and report the speedup. Default: "
             "bf16 for throughput modes, fp32 for recovery (the recovery "
             "row's historical config; pass bf16 to drill the bf16 "
             "snapshot round trip under preemption)",
    )
    p.add_argument(
        "--batch", type=int, default=0,
        help="learner mode: override batch_size (shape-granularity probe; "
             "0 = best-of-matrix sweep over {64, 128})",
    )
    p.add_argument(
        "--plane", default="device", choices=["device", "tiered"],
        help="learner mode: replay plane under the bench — device (HBM "
             "store, fused in-jit gather) or tiered (full-capacity host "
             "store + double-buffered HBM staging pipeline)",
    )
    p.add_argument(
        "--capacity", type=int, default=2_000_000,
        help="tiered plane: replay capacity in transitions (host RAM)",
    )
    p.add_argument(
        "--priority-plane", default="host", choices=["host", "device"],
        help="system mode: where the prioritized sum tree lives — host "
             "(numpy tree, per-update host fence) or device (HBM tree, "
             "in-jit sampling + write-back via the megastep superstep). "
             "The round-9 A/B arm",
    )
    p.add_argument(
        "--superstep", type=int, default=1,
        help="system mode with --priority-plane device: chain N fused "
             "K-update dispatches per host re-entry "
             "(config.superstep_dispatches)",
    )
    p.add_argument(
        "--sessions", type=int, default=0,
        help="serve mode: stateful client session population (0 = auto: "
             "256 open-loop so sessions ≫ cache capacity, 32 closed-loop)",
    )
    p.add_argument(
        "--serve-seconds", type=float, default=30.0,
        help="serve mode: measurement window (a hot reload fires "
             "halfway); with --rate-search, the length of EACH probed "
             "rate window (pass something small, e.g. 5)",
    )
    p.add_argument(
        "--arrival-rate", type=float, default=200.0,
        help="serve mode: open-loop Poisson arrival rate in requests/s — "
             "offered load does not throttle when the server queues, so "
             "tail latency under overload is measured honestly. 0 = the "
             "legacy closed-loop session threads",
    )
    p.add_argument(
        "--slo-ms", type=float, default=50.0,
        help="serve mode: latency SLO for the slo_attainment row "
             "(fraction of post-warmup requests answered within this; "
             "rejected/errored requests count as misses)",
    )
    p.add_argument(
        "--rate-search", action="store_true",
        help="serve mode: replace the fixed-rate load arms with a "
             "max-sustained-rate search (double then bisect) A/B'ing the "
             "staged serve pipeline (config.serve_pipeline) against the "
             "serial path, plus a bitwise action-parity probe and a "
             "pipeline-on replica-kill cell — emits the "
             "serve_max_rate_at_slo row",
    )
    p.add_argument(
        "--slo-target", type=float, default=0.99,
        help="serve mode --rate-search: SLO attainment a rate window "
             "must reach to count as sustained",
    )
    p.add_argument(
        "--rate-start", type=float, default=32.0,
        help="serve mode --rate-search: first probed arrival rate in "
             "requests/s (doubles until the SLO breaks, then bisects)",
    )
    p.add_argument(
        "--serve-out", default="",
        help="serve mode --rate-search: also write the report JSON here "
             "(e.g. BENCH_r15.json)",
    )
    p.add_argument(
        "--serve-devices", type=int, default=1,
        help="serve mode: replicate the serve stack over N local devices "
             "with session-affinity routing (serve/multi.py)",
    )
    p.add_argument(
        "--scenario-rate", type=float, default=100.0,
        help="scenarios mode: base arrival rate in requests/s (scenario "
             "profiles multiply this: diurnal peaks at 3x, flash crowd "
             "bursts to 8x)",
    )
    p.add_argument(
        "--scenario-seconds", type=float, default=4.0,
        help="scenarios mode: duration of EACH scenario's offered-load "
             "window (the matrix runs 6 scenarios x 4 rungs)",
    )
    p.add_argument(
        "--scenario-sessions", type=int, default=64,
        help="scenarios mode: concurrent session slots per scenario",
    )
    p.add_argument(
        "--scenario-seed", type=int, default=0,
        help="scenarios mode: base seed for the deterministic arrival "
             "traces (each built-in scenario offsets it)",
    )
    p.add_argument(
        "--scenario-out", default="",
        help="scenarios mode: also write the readiness report JSON here "
             "(e.g. BENCH_r11.json)",
    )
    p.add_argument(
        "--autoscale-seconds", type=float, default=16.0,
        help="autoscale mode: diurnal scenario duration (long enough for "
             "the crest to buy a replica and the falling edge to drain "
             "it)",
    )
    p.add_argument(
        "--autoscale-rate", type=float, default=0.0,
        help="autoscale mode: diurnal BASE rate in requests/s (peak is "
             "3x); 0 auto-calibrates to half of one replica's measured "
             "capacity",
    )
    p.add_argument(
        "--autoscale-sessions", type=int, default=64,
        help="autoscale mode: concurrent session slots",
    )
    p.add_argument(
        "--autoscale-seed", type=int, default=0,
        help="autoscale mode: seed for the deterministic arrival trace",
    )
    p.add_argument(
        "--autoscale-out", default="",
        help="autoscale mode: also write the report JSON here "
             "(e.g. BENCH_r17.json)",
    )
    p.add_argument(
        "--liveloop-rate", type=float, default=60.0,
        help="liveloop mode: fixed aggregate arrival rate in requests/s "
             "(Poisson-paced per session)",
    )
    p.add_argument(
        "--liveloop-seconds", type=float, default=30.0,
        help="liveloop mode: wall-clock window for the closed loop "
             "(long enough for learning_starts + >= 1 checkpoint reload)",
    )
    p.add_argument(
        "--liveloop-sessions", type=int, default=8,
        help="liveloop mode: concurrent live sessions (each a closed-loop "
             "catch episode stream)",
    )
    p.add_argument(
        "--liveloop-seed", type=int, default=0,
        help="liveloop mode: seed for traffic pacing, envs, and the "
             "per-session exploration assignment",
    )
    p.add_argument(
        "--liveloop-out", default="",
        help="liveloop mode: also write the report JSON here "
             "(e.g. BENCH_r12.json)",
    )
    p.add_argument(
        "--podloop-hosts", type=int, default=2,
        help="podloop mode: serve-host process count feeding the learner",
    )
    p.add_argument(
        "--podloop-sessions", type=int, default=8,
        help="podloop mode: concurrent driver sessions (split across "
             "hosts round-robin)",
    )
    p.add_argument(
        "--podloop-seconds", type=float, default=90.0,
        help="podloop mode: wall-clock window (long enough for the "
             "SIGKILL'd host to relaunch, reconnect, and resume its "
             "stream before the end)",
    )
    p.add_argument(
        "--podloop-rate", type=float, default=60.0,
        help="podloop mode: aggregate closed-loop arrival rate in "
             "requests/s",
    )
    p.add_argument(
        "--podloop-seed", type=int, default=0,
        help="podloop mode: seed for traffic pacing, envs, and the "
             "children's exploration/jitter streams",
    )
    p.add_argument(
        "--podloop-out", default="",
        help="podloop mode: also write the report JSON here "
             "(e.g. BENCH_r18.json)",
    )
    p.add_argument(
        "--replay-scale", type=int, default=10,
        help="replay-scale mode: total retention as a multiple of the "
             "host-slab capacity (the disk tier holds the excess)",
    )
    p.add_argument(
        "--replay-scale-sessions", type=int, default=6,
        help="replay-scale mode: liveloop rerun session count",
    )
    p.add_argument(
        "--replay-scale-seconds", type=float, default=25.0,
        help="replay-scale mode: liveloop rerun wall-clock window",
    )
    p.add_argument(
        "--replay-scale-out", default="BENCH_r19.json",
        help="replay-scale mode: report JSON path ('' to skip the file)",
    )
    args = p.parse_args()
    enable_compilation_cache()
    precision = args.precision or (
        "fp32" if args.mode == "recovery" else "bf16"
    )
    if args.mode == "multitask":
        multitask_main(
            updates=args.mt_updates,
            eval_episodes=args.mt_eval_episodes,
            out_path=args.mt_out,
        )
    elif args.mode == "recovery":
        recovery_main(precision)
    elif args.mode == "serve":
        if args.rate_search:
            serve_rate_search_main(
                args.core, args.lru_chunk,
                sessions=args.sessions or 64,
                seconds=args.serve_seconds,
                slo_ms=args.slo_ms, slo_target=args.slo_target,
                start_rate=args.rate_start, out_path=args.serve_out,
            )
        else:
            serve_main(args.core, args.lru_chunk, args.sessions,
                       args.serve_seconds, precision,
                       arrival_rate=args.arrival_rate, slo_ms=args.slo_ms,
                       devices=args.serve_devices)
    elif args.mode == "liveloop":
        liveloop_main(args.core, args.lru_chunk,
                      sessions=args.liveloop_sessions,
                      seconds=args.liveloop_seconds,
                      arrival_rate=args.liveloop_rate,
                      seed=args.liveloop_seed,
                      out_path=args.liveloop_out)
    elif args.mode == "podloop":
        podloop_main(hosts=args.podloop_hosts,
                     sessions=args.podloop_sessions,
                     seconds=args.podloop_seconds,
                     arrival_rate=args.podloop_rate,
                     seed=args.podloop_seed,
                     out_path=args.podloop_out)
    elif args.mode == "replay-scale":
        replay_scale_main(scale=args.replay_scale,
                          sessions=args.replay_scale_sessions,
                          seconds=args.replay_scale_seconds,
                          out_path=args.replay_scale_out)
    elif args.mode == "scenarios":
        scenarios_main(args.core, args.lru_chunk,
                       sessions=args.scenario_sessions,
                       seconds=args.scenario_seconds,
                       base_rate=args.scenario_rate, slo_ms=args.slo_ms,
                       out_path=args.scenario_out, seed=args.scenario_seed)
    elif args.mode == "autoscale":
        autoscale_main(args.core, args.lru_chunk,
                       sessions=args.autoscale_sessions,
                       seconds=args.autoscale_seconds,
                       base_rate=args.autoscale_rate, slo_ms=args.slo_ms,
                       out_path=args.autoscale_out,
                       seed=args.autoscale_seed)
    elif args.mode == "system":
        system_main(args.core, args.lru_chunk, precision,
                    args.priority_plane, args.superstep)
    elif args.mode == "fused":
        fused_system_main(args.collect_every, args.core, args.lru_chunk,
                          precision)
    elif args.mode == "long_context":
        long_context_main(args.core, args.lru_chunk, precision)
    elif args.plane == "tiered":
        tiered_main(args.core, args.lru_chunk, args.batch, args.capacity,
                    precision=precision)
    else:
        learner_matrix_main(args.core, args.lru_chunk, args.batch, precision)
    log_compile_cache_stats()
