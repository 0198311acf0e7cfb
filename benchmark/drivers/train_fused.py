"""Driver kind `train_fused`: the whole actor-learner loop, as one process.

The body of `Trainer.run_fused` (train.py) driven from here: `Trainer(cfg)`,
`warmup()` (ring fill by on-device collection), then `FusedSystemRunner` or
`ShardedFusedRunner.step` in a loop, paced by the program's own
samples_per_insert rule. A closed system: there is no client.

Copied from bench.py::fused_system_main, with its count repaired: env steps
are counted 1:1 (it multiplied by an Atari frameskip of 4 on an env that has
none), and the configurations use an env whose episodes fill every block (its
82-step catch episodes sat in 400-step slots, so most "learned" steps were
masked padding).

The window covers whole collect periods. The pacer collects in bursts (two
consecutive collecting dispatches, because chunk accounting lags one
dispatch), so a period boundary is the first dispatch that records no chunk
after one that did: there the consumed:inserted ratio is back where it was at
the previous boundary. The window opens at the first boundary (both dispatch
variants have run by then), and closes at the last boundary before
`--seconds` is up; each end is a readback of `state.step`, so every dispatch
counted has finished on the device.

`correct`'s reference check is judged at the window's START state: the
parameters and a batch of stored sequences are taken after `setup_s` is
stamped and before the window opens. Warm-up ends on the pacer's counters
(the first period boundary), not on the clock, so a slow program and a fast
one, a parent and a change, are all checked after the same number of updates.
The comparison itself runs after the window. The same comparison at the END
state (`notes.checks.reference_end`) is judged on Q alone, which is measured
on the tensor's own scale; its loss and gradient norm move with how far the
window trained and are recorded.
The loss island is judged alone too, at the start state.

The comparisons run with the program's device state RELEASED. Everything that
needs the program on the device (set-up, the two captures, the window) happens
in `_drive`, which hands back host arrays, numbers and `trainer.net` (a module,
no arrays). When it has returned nothing references the trainer, the runner,
the train state, the replay stores or the collector's carry, and `run` collects
them (`gc.collect()`) before it builds the first comparison: the check then needs room for the
uploaded parameters, their gradients and the activations of 8 sequences, not
for those beside a learner (PERF.md section 7: the sizing rule).
`notes.window_resident_gb` is what the device held after the window,
`notes.check_resident_gb` what it holds just before the first comparison, and
`notes.check_peak_gb` the process's peak after the last.
"""

from __future__ import annotations

import contextlib
import gc
import time
from typing import List

import numpy as np

from benchmark import correct, harness


def _make_runner(trainer, cfg):
    from r2d2_tpu.megastep import FusedSystemRunner, ShardedFusedRunner

    common = dict(collect_every=1, chunk_len=trainer.actor.chunk,
                  sample_rng=trainer.sample_rng, samples_per_insert=cfg.samples_per_insert)
    args = (cfg, trainer.net, trainer.fn_env, trainer.replay, trainer.actor.epsilons,
            trainer.actor.env_state, trainer.actor.key)
    if cfg.replay_plane == "sharded":
        return ShardedFusedRunner(*args, trainer.mesh, **common)
    if cfg.replay_plane == "device":
        return FusedSystemRunner(*args, **common)
    raise harness.BenchmarkError(f"train_fused needs replay_plane device|sharded, got {cfg.replay_plane}")


def _sync(state) -> int:
    return int(np.asarray(state.step))


def _planes(replay) -> list:
    return list(getattr(replay, "shards", [replay]))


def valid_step_share(replay, learning_steps: int) -> float:
    """Stored learning steps over (stored sequences x learning_steps), from
    the control plane's own per-block counters: the share of a sampled
    window that is not padding."""
    steps = seqs = 0
    for p in _planes(replay):
        occ = np.asarray(p.occupied, bool)
        steps += int(np.asarray(p.learning_sum)[occ].sum())
        seqs += int(np.asarray(p.num_seq_store)[occ].sum())
    return steps / max(seqs * learning_steps, 1)


def _sample_batch(cfg, trainer, gather, n: int, seed: int):
    """`n` stored sequences drawn through the replay's own sampler with a
    generator of the benchmark's (the trainer's stream is left alone; a draw
    changes nothing in the replay), gathered from the device store by
    `gather` (the jitted learner.make_store_gather) and brought to the host."""
    import jax
    import jax.numpy as jnp

    idx = trainer.replay.sample_indices(np.random.default_rng(seed))
    b, s = np.asarray(idx.b), np.asarray(idx.s)
    if b.ndim == 2:  # sharded: (dp, B/dp) block slots LOCAL to each shard
        per = cfg.num_blocks // b.shape[0]
        b = b + (np.arange(b.shape[0]) * per)[:, None]
    b, s = b.reshape(-1)[:n].astype(np.int32), s.reshape(-1)[:n].astype(np.int32)
    batch = trainer.replay.run_with_stores(
        lambda st: gather(st, jnp.asarray(b), jnp.asarray(s), jnp.ones(b.shape[0], jnp.float32)))
    return jax.device_get(batch)  # host arrays: the check then runs on one device


def _operating_point(cfg, trainer, gather, state, n_seq: int, seed: int) -> dict:
    """What the reference check is asked at: the online and target parameters
    as they are now (host copies: the runners donate their state) and `n_seq`
    stored sequences drawn now."""
    import jax

    params, target_params = jax.device_get((state.params, state.target_params))
    return {"updates": _sync(state), "params": params, "target_params": target_params,
            "batch": _sample_batch(cfg, trainer, gather, n_seq, seed)}


def _stall_notes(boundaries: List[float], elapsed: float, steps: int) -> dict:
    """Where a window's time went when it reads low: a period is the same work
    every time (the device is never idle and the pacer counts work, not time),
    so a period longer than the median one held a pause of the host that
    outlasted the one dispatch the program keeps in flight. `stall_s` is the
    periods' time beyond the median period's, summed, and `steady_steps_per_s`
    the window's work over its time less that: notes only, beside
    `learn_steps_per_s`, which stays all the work over all the time."""
    periods = np.diff(np.asarray([0.0] + list(boundaries)))
    if periods.size == 0:
        return {}
    median = float(np.median(periods))
    stall_s = float(np.clip(periods - median, 0.0, None).sum())
    return {"periods": int(periods.size), "period_s_median": median, "period_s_max": float(periods.max()),
            "stall_s": stall_s, "steady_steps_per_s": steps / (elapsed - stall_s)}


def _drive(ctx: harness.Context) -> dict:
    """Everything that needs the program on the device: set-up, warm-up, the
    start capture, the window, the end capture. Returns what `run` judges and
    reports: host arrays, numbers and `trainer.net`, and nothing that holds a
    device buffer of the program."""
    import jax

    from r2d2_tpu.learner import make_store_gather
    from r2d2_tpu.train import Trainer

    cell, tr_cfg = ctx.cell, ctx.cell.traffic
    extra = {
        "samples_per_insert": float(tr_cfg["samples_per_insert"]),
        "training_steps": 10**9, "save_interval": 10**9, "log_interval": 3600.0,
        "checkpoint_dir": ctx.work_dir("ckpt", cell.name), "metrics_path": None,
    }
    cfg = harness.build_config(cell.config, ctx.seed, extra)
    cfg = cfg.replace(learning_starts=int(cfg.buffer_capacity * float(tr_cfg["fill_fraction"])))
    ctx.cfg = cfg
    runtime = harness.check_runtime(cfg, cell.workload["chips"], ctx.require_tpu)
    print(f"[bench] {cell.name} runtime {runtime}", flush=True)

    trainer = Trainer(cfg)
    t = time.perf_counter()
    trainer.warmup()
    fill_s = time.perf_counter() - t
    print(f"[bench] ring filled to {len(trainer.replay)} of {cfg.buffer_capacity} "
          f"in {fill_s:.1f}s", flush=True)
    runner = _make_runner(trainer, cfg)
    state = trainer.state
    K = cfg.updates_per_dispatch
    prev_rec = 0

    def at_boundary(rec: int) -> bool:
        """True on the first dispatch that records no chunk after one that
        did (every dispatch when the pacer is off and each one collects)."""
        nonlocal prev_rec
        hit = cfg.samples_per_insert <= 0 or (rec == 0 and prev_rec > 0)
        prev_rec = rec
        return hit

    # ---- warm-up: until the first period boundary (both variants have run)
    losses: List[object] = []
    seen_collect, warm = False, 0
    while True:
        state, m, rec = runner.step(state)
        warm += 1
        seen_collect |= rec > 0
        if at_boundary(rec) and seen_collect:
            break
        if warm > int(tr_cfg.get("max_warm_dispatches", 400)):
            raise harness.BenchmarkError("no collect period boundary during warm-up")
    _sync(state)
    ctx.counters["cli.compile_misses"] = harness.compile_misses()
    setup_s = time.perf_counter() - ctx.t_start

    # ---- the reference check's operating point: after the stamp (it is the
    # benchmark's work, not the program's set-up), before the window opens
    n_seq = int(tr_cfg.get("correct_sequences", 8))
    gather = jax.jit(make_store_gather(cfg))
    start = _operating_point(cfg, trainer, gather, state, n_seq, ctx.seed)
    capture_s = time.perf_counter() - ctx.t_start - setup_s
    compiles0 = harness.compile_requests()

    # ---- the window: whole periods, at most `seconds` (one period at least)
    budget = ctx.seconds
    if ctx.trace:
        budget = min(budget, float(tr_cfg.get("trace_seconds", 8.0)))
    dispatches = 0
    period_s, last_boundary_t = None, 0.0
    boundaries: List[float] = []  # seconds from t0 at which each period closed: `_stall_notes`
    with harness.Tracer(ctx) if ctx.trace else contextlib.nullcontext():
        t0 = time.perf_counter()
        while True:
            with harness.span("bench.step"):
                state, m, rec = runner.step(state)
            dispatches += 1
            losses.append(m["loss"])
            if not at_boundary(rec):
                continue
            now = time.perf_counter() - t0
            period_s, last_boundary_t = now - last_boundary_t, now
            boundaries.append(now)
            if now + period_s > budget:
                break
        with harness.span("bench.sync"):
            _sync(state)
        elapsed = time.perf_counter() - t0
    compiles_in_window = harness.compile_requests() - compiles0
    ctx.counters["memory_peak_bytes"] = harness.memory_peak_bytes()
    runner.finish()

    updates = dispatches * K
    steps_per_update = cfg.batch_size * cfg.learning_steps
    loss_host = np.asarray(jax.device_get(losses), np.float32)
    share = valid_step_share(trainer.replay, cfg.learning_steps)
    ctx.counters.update({
        "updates": updates, "dispatches": dispatches, "window_s": elapsed,
        "updates_per_s": updates / elapsed, "replay.valid_step_share": 100.0 * share,
        "compiles_in_window": compiles_in_window,
    })

    # ---- the END operating point, while the program is still there to be asked
    end = _operating_point(cfg, trainer, gather, state, n_seq, ctx.seed)
    print(f"[bench] {updates} updates in {elapsed:.2f}s over {dispatches} dispatches, "
          f"period {period_s:.2f}s, loss {loss_host[-1]:.5f}", flush=True)
    finite = np.isfinite(loss_host)
    return {
        "cfg": cfg, "net": trainer.net, "start": start, "end": end,
        "attempted": updates, "failed": int((~finite).sum()) * K,
        "sound": bool(finite.all() and compiles_in_window == 0
                      and share >= float(tr_cfg.get("min_valid_step_share", 0.0))),
        "end_to_end": {"learn_steps_per_s": updates * steps_per_update / elapsed, "setup_s": setup_s},
        "notes": {"window_s": elapsed, "dispatches": dispatches, "period_s": period_s,
                  "ring_fill_s": fill_s, "valid_step_share": share,
                  "compiles_in_window": compiles_in_window, "runtime": runtime,
                  "loss_last": float(loss_host[-1]), "warm_dispatches": warm,
                  "start_capture_s": capture_s,
                  **_stall_notes(boundaries, elapsed, updates * steps_per_update),
                  "window_resident_gb": harness.device_bytes_in_use() / 1e9},
    }


def run(ctx: harness.Context) -> harness.Measured:
    cell = ctx.cell
    ref = harness.reference_for(cell)
    d = _drive(ctx)
    cfg, start, end = d["cfg"], d["start"], d["end"]

    # ---- correct: outside the window, and without the program on the device:
    # `_drive`'s frame was the last holder of trainer, runner, state and stores,
    # and trainer and runner point at each other, so their buffers wait for the collector
    gc.collect()
    resident_gb = harness.device_bytes_in_use() / 1e9
    print(f"[bench] device memory in use: {d['notes']['window_resident_gb']:.3f} GB after the window, "
          f"{resident_gb:.3f} GB before the first comparison", flush=True)
    # the reference check is judged at the window's start state and recorded at its end state
    check = correct.ReferenceCheck(ref, cfg, d["net"], cell.config)
    checks = {
        "kernels": correct.kernel_checks(ref, cfg, ctx.seed, max(cfg.batch_size // max(cfg.dp_size, 1), 1)),
        "reference": check(start["params"], start["target_params"], start["batch"]),
        "reference_end": check(end["params"], end["target_params"], end["batch"], judged=correct.END_STATE),
        "loss_island": correct.loss_island(ref, cfg, start["params"], start["target_params"], start["batch"]),
    }
    checks["reference"]["updates_at_check"] = start["updates"]
    checks["reference_end"]["updates_at_check"] = end["updates"]
    print(f"[bench] checks {checks}", flush=True)
    from r2d2_tpu.utils.compilation_cache import log_compile_cache_stats

    log_compile_cache_stats()
    return harness.Measured(
        correct=d["sound"] and all(c["ok"] for c in checks.values()),
        attempted=d["attempted"], failed=d["failed"], end_to_end=d["end_to_end"],
        notes={"checks": checks, **d["notes"], "check_resident_gb": resident_gb,
               "check_peak_gb": harness.memory_peak_bytes() / 1e9},
    )
