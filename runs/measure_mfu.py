"""MFU: what fraction of the chip the learner dispatch actually uses.

Round-3 verdict item 6: BENCH proves the system is fast vs the
reference's implied rate (17.2x), but never states utilization vs the
HARDWARE. This measures it for the exact dispatch bench.py's headline
times — make_fused_multi_train_step (K prioritized double-Q updates in
one jitted scan) against a synthetically filled HBM replay:

- FLOPs per dispatch from XLA's own cost model: the script re-invokes
  itself with --cost-only, which pins the CPU platform and reads
  `jitted.lower(...).cost_analysis()["flops"]` PRE-compile — a
  client-side analytic pass over the same HLO (shape-determined, so
  platform-independent). NOTE the child is started after this process
  has initialised jax: it must stay on the CPU (a chip belongs to one
  process), which --cost-only's platform pin ensures;
- wall time per dispatch with the readback sync bench.py uses;
- MFU = achieved FLOP/s / peak. Peak defaults to 197e12 (TPU v5e
  bf16 per chip, public spec) and is applied to WHATEVER device runs
  this — a rounds-4/5 builder script, not a benchmark; the peaks table
  keyed by device_kind is ROADMAP S1's. Override with --peak-tflops.

Also prints an ANALYTIC per-component forward-FLOP table (Nature conv
trunk layer by layer, recurrent core, dueling heads) so the dominant
kernel is named, not guessed — the conv trunk's share decides whether
chasing the encoder (verdict item 7) has headroom.

    python runs/measure_mfu.py --out runs/mfu.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np


def nature_encoder_flops_per_frame(obs_hw=(84, 84), latent=512):
    """Analytic MACs*2 for the Nature trunk at VALID padding (the exact
    geometry of models/encoders.py NatureEncoder; reference model.py:47-57).
    Returns (total, rows) with one row per layer."""
    H, W = obs_hw
    rows = []
    cin = 1
    total = 0
    for name, k, s, cout in (("conv1", 8, 4, 32), ("conv2", 4, 2, 64), ("conv3", 3, 1, 64)):
        H = (H - k) // s + 1
        W = (W - k) // s + 1
        f = H * W * cout * (k * k * cin) * 2
        rows.append({"layer": name, "out": f"{H}x{W}x{cout}", "mflops_per_frame": round(f / 1e6, 2)})
        total += f
        cin = cout
    dense = H * W * cin * latent * 2
    rows.append({"layer": "enc_dense", "out": f"{latent}", "mflops_per_frame": round(dense / 1e6, 2)})
    total += dense
    return total, rows


def core_flops_per_step(cfg):
    """Matmul MACs*2 per sequence step for the configured recurrent core
    (elementwise recurrence work excluded — it is bandwidth, not MXU)."""
    H = cfg.hidden_dim
    D = H + cfg.action_dim + 1  # concat(latent, one-hot action, reward)
    if cfg.recurrent_core == "lru":
        # in_re/in_im (D,H) + out_re/out_im (H,H) + skip (D,H)
        f = 2 * (2 * D * H + 2 * H * H + D * H)
        if cfg.lru_chunk > 0:
            # chunked formulation: 4 causal (C,C,H) einsums per chunk =
            # 4*C*H MACs per step amortized (counting the masked zeros XLA
            # actually multiplies)
            f += 2 * 4 * cfg.lru_chunk * H
        return f
    # LSTM: wi (D,4H) + wh (H,4H)
    return 2 * (D + H) * 4 * H


def heads_flops_per_step(cfg):
    H, A = cfg.hidden_dim, cfg.action_dim
    return 2 * (H * H + H * H + H * A + H)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=None)
    p.add_argument("--K", type=int, default=16)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--peak-tflops", type=float, default=197.0,
                   help="chip peak dense TFLOP/s for the MFU denominator "
                        "(197 = TPU v5e bf16)")
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes + 2s window: plumbing check "
                        "(the MFU number itself is meaningless off-chip)")
    p.add_argument("--cost-only", action="store_true",
                   help="internal: pin CPU, print the per-dispatch FLOP "
                        "count from the pre-compile cost model, exit")
    p.add_argument("--core", default="lstm", choices=["lstm", "lru"],
                   help="recurrent core of the measured dispatch")
    p.add_argument("--lru-chunk", type=int, default=0,
                   help="LRU formulation: 0 = scan, N > 0 = chunked MXU")
    p.add_argument("--batch", type=int, default=0,
                   help="override batch_size (0 = preset default)")
    args = p.parse_args()

    if args.cost_only:
        jax.config.update("jax_platforms", "cpu")

    from bench import synth_block
    from r2d2_tpu.config import default_atari
    from r2d2_tpu.learner import init_train_state, make_fused_multi_train_step
    from r2d2_tpu.replay.device_store import DeviceReplayBuffer

    cfg = default_atari().replace(
        compute_dtype="bfloat16", buffer_capacity=100_000,
        recurrent_core=args.core,
        lru_chunk=args.lru_chunk if args.core == "lru" else 0,
    )
    if args.batch:
        cfg = cfg.replace(batch_size=args.batch)
    if args.smoke:
        cfg = cfg.replace(
            obs_shape=(84, 84, 1), batch_size=4, buffer_capacity=8_000,
            learning_starts=2_000, num_actors=2,
        )
        args.K = min(args.K, 2)
        args.seconds = min(args.seconds, 2.0)
    if args.cost_only:
        # FLOP totals depend on batch/seq/net shapes, not store capacity;
        # a small store keeps this pass light
        cfg = cfg.replace(buffer_capacity=8_000, learning_starts=2_000)
    K = args.K
    rng = np.random.default_rng(0)
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})", file=sys.stderr)

    print("filling replay...", file=sys.stderr, flush=True)
    replay = DeviceReplayBuffer(cfg)
    for _ in range(cfg.learning_starts // cfg.block_length + 5):
        replay.add_block(
            synth_block(cfg, rng),
            rng.uniform(0.5, 2.0, size=cfg.seqs_per_block).astype(np.float32),
            None,
        )
    assert replay.can_sample()
    print("replay filled", file=sys.stderr, flush=True)

    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    multi_step = make_fused_multi_train_step(cfg, net, K, donate=False)
    sample_rng = np.random.default_rng(1)
    draws = [replay.sample_indices(sample_rng) for _ in range(K)]
    b = jax.device_put(np.stack([d.b for d in draws]))
    s = jax.device_put(np.stack([d.s for d in draws]))
    w = jax.device_put(np.stack([d.is_weights for d in draws]))

    if args.cost_only:
        # K is forced to 1 here: the pre-compile cost model counts a
        # lax.scan BODY once regardless of trip count (verified: K=16
        # lowering reports ~1 update's FLOPs), so the parent scales the
        # single-update count by its K explicitly.
        ca = multi_step.lower(state, replay.stores, b, s, w).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        print(f"COST_FLOPS {float(ca.get('flops', float('nan')))}")
        return

    # per-UPDATE FLOP count via the CPU-pinned child (same shapes, same
    # HLO pass), scaled by this run's K
    import subprocess

    xla_flops_per_update = float("nan")
    try:
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--cost-only",
             "--K", "1", "--core", args.core,
             "--lru-chunk", str(args.lru_chunk),
             "--batch", str(args.batch)] + (["--smoke"] if args.smoke else []),
            capture_output=True, text=True, timeout=900,
        )
        for line in child.stdout.splitlines():
            if line.startswith("COST_FLOPS "):
                xla_flops_per_update = float(line.split()[1])
        if not np.isfinite(xla_flops_per_update):
            print(
                f"cost-only child failed:\n{child.stdout}\n{child.stderr[-2000:]}",
                file=sys.stderr,
            )
    except subprocess.TimeoutExpired:
        # fall through: the timing window below needs no child data
        print("cost-only child timed out after 900s", file=sys.stderr)
    xla_flops_per_dispatch = xla_flops_per_update * K

    # timed window (state NOT donated so the same args re-dispatch).
    # FIXED dispatch count, synced at the end: a wall-clock-bounded loop
    # without backpressure enqueues free (dispatch returns at enqueue on
    # this backend) and then drains for minutes — the wedge chains A-C
    # hit. n is sized from a 3-dispatch calibration to fill ~args.seconds.
    print("compiling timed dispatch...", file=sys.stderr, flush=True)
    out = multi_step(state, replay.stores, b, s, w)
    _ = int(np.asarray(out[0].step))  # compile+sync
    t0 = time.perf_counter()
    for _ in range(3):
        out = multi_step(state, replay.stores, b, s, w)
    _ = int(np.asarray(out[0].step))
    per = (time.perf_counter() - t0) / 3
    n = max(int(args.seconds / per), 5)
    print(f"calibrated {per*1e3:.0f} ms/dispatch; timing {n}...",
          file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    for _ in range(n):
        out = multi_step(state, replay.stores, b, s, w)
    _ = int(np.asarray(out[0].step))
    elapsed = time.perf_counter() - t0

    dispatches_per_s = n / elapsed
    updates_per_s = dispatches_per_s * K
    achieved = xla_flops_per_dispatch * dispatches_per_s
    peak = args.peak_tflops * 1e12
    mfu = achieved / peak

    # analytic forward breakdown: where the FLOPs are, per net evaluation
    enc_total, enc_rows = nature_encoder_flops_per_frame(
        cfg.obs_shape[:2], cfg.hidden_dim
    )
    core = core_flops_per_step(cfg)
    heads = heads_flops_per_step(cfg)
    per_step = enc_total + core + heads
    breakdown = enc_rows + [
        {"layer": f"core_{cfg.recurrent_core}", "mflops_per_frame": round(core / 1e6, 2)},
        {"layer": "dueling_heads", "mflops_per_frame": round(heads / 1e6, 2)},
    ]
    for r in breakdown:
        r["share"] = round(float(r["mflops_per_frame"]) * 1e6 / per_step, 3)
    dominant = max(breakdown, key=lambda r: r["share"])
    # 2 full-sequence evals per update (online w/ grad + target fwd-only):
    # fwd_target + fwd_online + bwd_online(~2x fwd) = 4x one forward
    analytic_per_update = 4 * cfg.batch_size * cfg.seq_len * per_step

    ok = np.isfinite(xla_flops_per_dispatch)
    row = {
        "metric": "learner_mfu",
        "updates_per_sec": round(updates_per_s, 2),
        # null (valid strict JSON), never NaN, when the child failed
        "xla_flops_per_dispatch": xla_flops_per_dispatch if ok else None,
        "achieved_tflops": round(achieved / 1e12, 2) if ok else None,
        "peak_tflops": args.peak_tflops,
        "mfu": round(mfu, 4) if ok else None,
        "analytic_flops_per_update": analytic_per_update,
        "analytic_vs_xla": round(
            analytic_per_update * K / xla_flops_per_dispatch, 3
        ) if ok else None,
        "dominant_component": dominant["layer"],
        "forward_breakdown": breakdown,
        "core": cfg.recurrent_core + (f"_c{cfg.lru_chunk}" if cfg.lru_chunk else ""),
        "K": K,
        "batch": cfg.batch_size,
        "seq_len": cfg.seq_len,
        "device": f"{dev.device_kind} ({dev.platform})",
    }
    print(json.dumps(row, allow_nan=False))
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(json.dumps(row, allow_nan=False) + "\n")
    if not ok:
        sys.exit(3)  # timing printed above; the chain must see the failure


if __name__ == "__main__":
    main()
