"""Microbench: recurrent-core unroll wall time vs sequence length.

The LRU core's claim is architectural: a diagonal linear recurrence
unrolls as ONE associative_scan (O(log T) dependent steps), while the
LSTM's nonlinear recurrence is inherently sequential (O(T)), Pallas
kernel or not. This measures exactly that on the real chip: forward
unroll time for the full R2D2Network (encoder + core + heads) at growing
T, one line of JSON per (core, T).

    python runs/bench_core_unroll.py --out runs/core_unroll.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def bench_one(cfg, B, T, iters=50):
    from r2d2_tpu.models.r2d2 import init_params

    net, params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    obs = jnp.asarray(rng.integers(0, 255, (B, T, *cfg.obs_shape), dtype=np.uint8))
    la = jnp.asarray(rng.integers(0, cfg.action_dim, (B, T)), jnp.int32)
    lr = jnp.asarray(rng.normal(size=(B, T)).astype(np.float32))
    hid = jnp.zeros((B, 2, cfg.hidden_dim), jnp.float32)
    burn = jnp.zeros(B, jnp.int32)
    learn = jnp.full(B, cfg.learning_steps, jnp.int32)
    fwd = jnp.full(B, cfg.forward_steps, jnp.int32)

    @jax.jit
    def fn(params, obs, la, lr, hid, burn, learn, fwd):
        q, _, _ = net.apply(params, obs, la, lr, hid, burn, learn, fwd)
        # scalar output: the end-of-window sync is one float readback
        return jnp.sum(q.astype(jnp.float32))

    args = (params, obs, la, lr, hid, burn, learn, fwd)
    float(fn(*args))  # compile + sync
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    float(out)  # host readback = device sync
    return (time.perf_counter() - t0) / iters


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=None)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--hidden", type=int, default=512)
    p.add_argument("--lens", default="128,256,512,1024")
    args = p.parse_args()

    from r2d2_tpu.config import R2D2Config

    rows = []
    for T in [int(x) for x in args.lens.split(",")]:
        # learning/forward fill the window; burn_in=0 keeps T the whole story
        base = dict(
            obs_shape=(84, 84, 1), action_dim=9, encoder="nature",
            hidden_dim=args.hidden, compute_dtype="bfloat16",
            burn_in_steps=0, learning_steps=T - 1, forward_steps=1,
            block_length=T - 1, buffer_capacity=(T - 1) * 4,
        )
        for core, extra in (
            ("lstm-pallas", dict(recurrent_core="lstm", lstm_backend="pallas")),
            ("lstm-scan", dict(recurrent_core="lstm", lstm_backend="scan")),
            ("lru", dict(recurrent_core="lru")),
            ("lru-c128", dict(recurrent_core="lru", lru_chunk=128)),
        ):
            cfg = R2D2Config(**base, **extra).validate()
            try:
                dt = bench_one(cfg, args.batch, T)
            except Exception as e:  # e.g. pallas unavailable off-TPU
                print(f"# skip {core} T={T}: {type(e).__name__}: {e}", file=sys.stderr)
                continue
            row = {
                "core": core, "T": T, "B": args.batch, "hidden": args.hidden,
                "ms_per_unroll": round(dt * 1e3, 3),
                "us_per_step_per_seq": round(dt * 1e6 / T / args.batch, 3),
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as fh:
            for row in rows:
                fh.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
