"""Driver kind `serve_open_loop`: the policy server under independent users.

In-process `PolicyServer.submit` (buckets, micro-batcher, session cache in
HBM, depth-2 pipeline) at a FIXED Poisson rate from the traffic file; every
session is created and stepped once during set-up, so the window sees no
admissions. Latency is timed from when each request was due (loadgen.py);
the end-to-end metric is p99 over the whole window, a request that failed
counting as a miss, and a window in which any request failed is not `correct`.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from benchmark import correct, harness, loadgen


def _stats_delta(after: Dict, before: Dict) -> Dict[str, float]:
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float)) and not isinstance(after[k], bool)
            and isinstance(before.get(k), (int, float))}


def _closed_rounds(server, ids, obs_of, reward_of, reset_first: bool, rounds: int, chunk: int = 512):
    """`rounds` sequential steps of every session in `ids`, each round sent in
    bounded chunks and waited for. Returns (q, action) arrays (S, rounds, ...)."""
    qs, acts = [], []
    for r in range(rounds):
        q_r, a_r = [], []
        for lo in range(0, len(ids), chunk):
            futs = [server.submit(ids[j], obs_of(j, r), reward=reward_of(j, r),
                                  reset=(reset_first and r == 0))
                    for j in range(lo, min(lo + chunk, len(ids)))]
            for f in futs:
                res = f.result(timeout=120.0)
                q_r.append(np.asarray(res.q, np.float32))
                a_r.append(int(res.action))
        qs.append(np.stack(q_r))
        acts.append(np.asarray(a_r, np.int32))
    return np.stack(qs, axis=1), np.stack(acts, axis=1)


def start_server(cfg, tc: Dict, seed: int):
    """The server as the traffic file sizes it, warmed and started; the
    weights come from the seed through the program's own initialiser."""
    import jax

    from r2d2_tpu.learner import init_train_state
    from r2d2_tpu.serve import PolicyServer, ServeConfig

    serve_cfg = ServeConfig(
        buckets=tuple(tc["buckets"]), max_wait_ms=float(tc["max_wait_ms"]),
        queue_depth=int(tc["queue_depth"]), cache_capacity=int(tc["cache_capacity"]),
        epsilon=0.0, seed=seed,
    )
    net, template = init_train_state(cfg, jax.random.PRNGKey(seed))
    server = PolicyServer(cfg, serve_cfg, params=template.params, net=net, template=template)
    server.warmup()
    server.start(watch_checkpoints=False)
    return server, template


def fill_sessions(server, cfg, sessions: int, rng):
    """Create every session and step it once (reset), so a window sees no
    admission; then send one burst of exactly b requests for every bucket b.
    `PolicyServer.warmup` compiles the bucket steps but not the eager
    `convert_element_type` its staging path runs per bucket shape, and a
    bucket first met inside the window would compile there (PERF.md 6).
    -> (ids, one fixed seeded observation per session)."""
    obs = rng.integers(0, 256, (sessions, *cfg.obs_shape), dtype=np.uint8)
    ids = [f"s{i}" for i in range(sessions)]
    _closed_rounds(server, ids, lambda j, r: obs[j], lambda j, r: 0.0, True, 1)
    for b in server.batcher.buckets:
        for _ in range(2):  # a burst the batcher split still lands in b once
            _closed_rounds(server, ids[:min(b, sessions)], lambda j, r: obs[j], lambda j, r: 0.0, False, 1)
    return ids, obs


def run(ctx: harness.Context) -> harness.Measured:
    from r2d2_tpu.serve import QueueFullError
    from r2d2_tpu.utils.compilation_cache import enable_compilation_cache, log_compile_cache_stats

    cell, tc = ctx.cell, ctx.cell.traffic
    enable_compilation_cache()  # as serve/__main__.py does; Trainer is not involved
    cfg = harness.build_config(cell.config, ctx.seed, {"serve_pipeline": bool(tc.get("pipeline", True))})
    ctx.cfg = cfg
    runtime = harness.check_runtime(cfg, cell.workload["chips"], ctx.require_tpu)
    print(f"[bench] {cell.name} runtime {runtime}", flush=True)
    sessions = int(tc["sessions"])
    server, template = start_server(cfg, tc, ctx.seed)
    try:
        rng = np.random.default_rng(ctx.seed)
        # ---- correct (3): sessions through cache + buckets vs the full unroll.
        # Before the fill, and evicted after: the cache holds exactly the
        # resident sessions, so these would push residents out.
        S, T = int(tc.get("correct_sessions", 4)), int(tc.get("correct_steps", 32))
        c_obs = rng.integers(0, 256, (S, T, *cfg.obs_shape), dtype=np.uint8)
        c_rew = rng.integers(0, 2, (S, T)).astype(np.float32)
        q_served, acts = _closed_rounds(
            server, [f"check{i}" for i in range(S)], lambda j, r: c_obs[j, r],
            lambda j, r: float(c_rew[j, r]), True, T)
        check = correct.serve_vs_reference(
            harness.reference_for(cell), cfg, template.params, c_obs, acts, c_rew, q_served)
        for i in range(S):
            server.evict(f"check{i}")

        t = time.perf_counter()
        ids, obs = fill_sessions(server, cfg, sessions, rng)
        fill_s = time.perf_counter() - t

        compiles0 = harness.compile_requests()
        ctx.counters["cli.compile_misses"] = harness.compile_misses()
        seconds = ctx.seconds
        if ctx.trace:
            seconds = min(seconds, float(tc.get("trace_seconds", 4.0)))
        sched = loadgen.poisson_schedule(ctx.seed + 1, float(tc["rate_per_s"]), seconds, sessions)
        before = server.stats()
        setup_s = time.perf_counter() - ctx.t_start

        submit = lambda i, sess: server.submit(ids[sess], obs[sess], reward=0.0, reset=False)
        t_window = time.perf_counter()
        if ctx.trace:
            with harness.Tracer(ctx):
                res = loadgen.run_open_loop(submit, sched, QueueFullError, span=harness.span)
        else:
            res = loadgen.run_open_loop(submit, sched, QueueFullError)
        after = server.stats()
        compiles_in_window = harness.compile_requests() - compiles0
        ctx.counters["memory_peak_bytes"] = harness.memory_peak_bytes()
    finally:
        server.stop()

    delta = _stats_delta(after, before)
    n = int(res.status.shape[0])
    failed = int((res.status != loadgen.OK).sum())
    # a failed, rejected or unanswered request counts as the whole window (a miss)
    pct = {q: loadgen.percentile_with_failures(res, float(q), fail_latency_s=seconds) * 1e3
           for q in (50, 90, 95, 99, 99.9)}
    over_slo = float(np.mean(~(res.latency_s <= float(tc.get("slo_ms", 20.0)) / 1e3))) if n else 0.0
    ctx.counters.update({
        "serve.batch_occupancy": (delta.get("requests", 0) / max(delta.get("batches", 0), 1)),
        "completed_batches": max(delta.get("completed_batches", 0), 1),
        "loadgen.late_p99_ms": float(np.percentile(res.late_s, 99.0) * 1e3) if n else 0.0,
        "compiles_in_window": compiles_in_window + delta.get("trace_count", 0),
        "window_s": res.elapsed_s,
    })
    # traffic is chosen so that no request fails: one that does spoils the run
    ok = check["ok"] and ctx.counters["compiles_in_window"] == 0 and failed == 0
    notes = {
        "check": check, "requests": n, "rate_offered_per_s": n / seconds,
        "p50_ms": pct[50], "p90_ms": pct[90], "p95_ms": pct[95], "p99_ms": pct[99], "p99.9_ms": pct[99.9],
        "over_slo_share": over_slo, "max_ms": float(np.nanmax(res.latency_s) * 1e3) if n else 0.0,
        "late_p99_ms": ctx.counters["loadgen.late_p99_ms"],
        "late_max_ms": float(res.late_s.max() * 1e3) if n else 0.0,
        # where the generator itself was held up > 10 ms, and the window's start on
        # CLOCK_MONOTONIC, so that a probe in another process can be set beside it
        "generator_stalls_due_s_late_ms": [(round(a, 3), round(b * 1e3, 1))
                                           for a, b in loadgen.generator_stalls(res, sched.due_s)[:20]],
        "window_t0_monotonic_s": t_window,
        "batch_occupancy": ctx.counters["serve.batch_occupancy"],
        "bucket_fill": after.get("bucket_fill"), "rejected": delta.get("rejected", 0),
        "deferrals": delta.get("deferrals", 0), "session_fill_s": fill_s,
        "cache_hit_rate": 100.0 * delta.get("cache_hits", 0)
        / max(delta.get("cache_hits", 0) + delta.get("cache_misses", 0), 1),
        "compiles_in_window": ctx.counters["compiles_in_window"], "runtime": runtime,
        "queue_depth_end": after.get("queue_depth"),
    }
    print(f"[bench] {n} requests at {tc['rate_per_s']}/s: p50 {pct[50]:.2f} p90 {pct[90]:.2f} p95 {pct[95]:.2f} "
          f"p99 {pct[99]:.2f} ms, failed {failed}, late p99 {notes['late_p99_ms']:.3f} ms, "
          f"occupancy {notes['batch_occupancy']:.2f}, check {check}", flush=True)
    log_compile_cache_stats()
    return harness.Measured(
        correct=ok, attempted=n, failed=failed,
        end_to_end={"serve_p99_ms": pct[99], "setup_s": setup_s}, notes=notes,
    )
