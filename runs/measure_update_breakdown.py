"""Wall-clock decomposition of the HEADLINE learner update (B=64, T=85,
Nature/512, auto→Pallas LSTM on TPU) into its components, on the real chip.

Four rounds of MFU analysis argued about where the 10.2 ms/update goes
(encoder shape granularity vs LSTM recurrence serialization) from FLOP
shares and bare-core microbenches. This measures the actual components at
the actual shapes, one line of JSON each:

  encoder fwd / fwd+bwd     Nature conv trunk over the (B*T, 84, 84, 4)
                            frame batch — the FLOP-dominant part
  core fwd / fwd+bwd        the LSTM over (B, T, 516) projected latents
                            (backend as resolved on this platform)
  unroll fwd / fwd+bwd      the full net (encoder + core + dueling heads,
                            both gather views) — fusion vs the parts
  loss fwd+bwd              learner loss_fn value_and_grad on a synthetic
                            DeviceBatch: online + target unrolls + TD loss
                            + priorities (everything but Adam/sync)
  train_step                one real update (adds Adam + target-sync select)

The residuals locate the time the FLOP ledger can't see:
  train_step - loss_fwd_bwd          = optimizer + sync overhead
  loss_fwd_bwd - (unroll fwd+bwd + unroll fwd)
                                     = loss/priority glue (should be ~0:
                                       XLA fuses it into the unrolls)
  unroll_fwd - (encoder_fwd + core_fwd + ...)   = fusion gain/loss

Timing protocol matches runs/bench_core_unroll.py: jit once, sync via a
scalar host readback, then iters timed calls ended by one readback.

Usage (chip must be idle — run inside a chain, not beside one):
    python runs/measure_update_breakdown.py --out runs/update_breakdown_r5.jsonl
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


def timed(fn, args, iters):
    float(fn(*args))  # compile + sync
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    float(out)  # host readback = device barrier
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def scalarize(x):
    # reduce any pytree/array output to one f32 scalar for the readback
    # sync. EVERY leaf must feed the scalar: summing a subset lets XLA
    # dead-code-eliminate the computations behind the dropped leaves,
    # which for grads would prune most of the backward pass being timed
    leaves = [jnp.sum(l.astype(jnp.float32)) for l in jax.tree.leaves(x)
              if hasattr(l, "astype")]
    return sum(leaves) if leaves else jnp.float32(0)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=None)
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                   help="pin the jax platform (same effect as exporting "
                        "JAX_PLATFORMS)")
    args = p.parse_args()

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from r2d2_tpu.config import default_atari
    from r2d2_tpu.learner import DeviceBatch, init_train_state, make_train_step
    from r2d2_tpu.models.encoders import make_encoder
    from r2d2_tpu.models.lstm import LSTM

    cfg = default_atari().replace(env_name="fake")
    B = cfg.batch_size
    T = cfg.burn_in_steps + cfg.learning_steps + cfg.forward_steps
    L = cfg.learning_steps
    H = cfg.hidden_dim
    D = H + cfg.action_dim + 1  # core input: latent + one-hot action + reward
    rng = np.random.default_rng(0)
    rows = []

    def emit(component, ms, **extra):
        row = {"component": component, "ms": round(ms, 4), "B": B, "T": T, **extra}
        rows.append(row)
        print(json.dumps(row), flush=True)

    # --- encoder: Nature trunk over the flattened frame batch ---
    enc = make_encoder(cfg.encoder, H, jnp.float32)
    frames = jnp.asarray(
        rng.integers(0, 255, (B * T, *cfg.obs_shape), dtype=np.uint8), jnp.float32
    ) / 255.0
    enc_params = enc.init(jax.random.PRNGKey(0), frames[:2])

    @jax.jit
    def enc_fwd(p, x):
        return jnp.sum(enc.apply(p, x).astype(jnp.float32))

    @jax.jit
    def enc_bwd(p, x):
        return scalarize(jax.grad(lambda p: jnp.sum(enc.apply(p, x)))(p))

    emit("encoder_fwd", timed(enc_fwd, (enc_params, frames), args.iters))
    emit("encoder_fwd_bwd", timed(enc_bwd, (enc_params, frames), args.iters))

    # --- core: the LSTM at learner shapes, backend as resolved here ---
    core = LSTM(hidden_dim=H, in_dim=D)
    xs = jnp.asarray(rng.normal(size=(B, T, D)), jnp.float32)
    carry = (jnp.zeros((B, H), jnp.float32), jnp.zeros((B, H), jnp.float32))
    core_params = core.init(jax.random.PRNGKey(1), xs, carry)

    @jax.jit
    def core_fwd(p, xs, carry):
        outs, _ = core.apply(p, xs, carry)
        return jnp.sum(outs.astype(jnp.float32))

    @jax.jit
    def core_bwd(p, xs, carry):
        return scalarize(
            jax.grad(lambda p: jnp.sum(core.apply(p, xs, carry)[0]))(p)
        )

    backend = "pallas" if jax.default_backend() == "tpu" else "scan"
    emit("core_fwd", timed(core_fwd, (core_params, xs, carry), args.iters),
         backend=backend)
    emit("core_fwd_bwd", timed(core_bwd, (core_params, xs, carry), args.iters),
         backend=backend)

    # --- full net unroll (both gather views), fwd and fwd+bwd ---
    from r2d2_tpu.models.r2d2 import init_params

    net, params = init_params(jax.random.PRNGKey(2), cfg)
    obs = jnp.asarray(rng.integers(0, 255, (B, T, *cfg.obs_shape), dtype=np.uint8))
    la = jnp.asarray(rng.integers(0, cfg.action_dim, (B, T)), jnp.int32)
    lr = jnp.asarray(rng.normal(size=(B, T)).astype(np.float32))
    hid = jnp.zeros((B, 2, H), jnp.float32)
    burn = jnp.full(B, cfg.burn_in_steps, jnp.int32)
    learn = jnp.full(B, L, jnp.int32)
    fwd_steps = jnp.full(B, cfg.forward_steps, jnp.int32)

    def q_sum(p):
        q, qb, _ = net.apply(p, obs, la, lr, hid, burn, learn, fwd_steps)
        return jnp.sum(q.astype(jnp.float32)) + jnp.sum(qb.astype(jnp.float32))

    unroll_fwd = jax.jit(q_sum)
    unroll_bwd = jax.jit(lambda p: scalarize(jax.grad(q_sum)(p)))

    emit("unroll_fwd", timed(unroll_fwd, (params,), args.iters))
    emit("unroll_fwd_bwd", timed(unroll_bwd, (params,), args.iters))

    # --- the real learner loss (online + target + TD + priorities) ---
    net2, state = init_train_state(cfg, jax.random.PRNGKey(3))
    batch = DeviceBatch(
        obs=obs,
        last_action=la,
        last_reward=lr,
        hidden=hid,
        action=jnp.asarray(rng.integers(0, cfg.action_dim, (B, L)), jnp.int32),
        n_step_reward=jnp.asarray(rng.normal(size=(B, L)).astype(np.float32)),
        gamma=jnp.full((B, L), cfg.gamma**cfg.forward_steps, jnp.float32),
        burn_in_steps=burn,
        learning_steps=learn,
        forward_steps=fwd_steps,
        is_weights=jnp.ones(B, jnp.float32),
    )
    from r2d2_tpu.learner import _raw_train_step

    raw = _raw_train_step(cfg, net2)

    # full step timed non-donated (fresh state each call, no aliasing).
    # The scalar must depend on the UPDATED state: reducing only
    # loss+priorities (forward-only values) lets XLA prune the whole
    # backward pass, Adam, and target-sync from the timed graph
    def step_scalar(s, b):
        new_state, metrics, priorities = raw(s, b)
        return (scalarize(new_state.params) + scalarize(metrics["loss"])
                + jnp.sum(priorities))

    emit("train_step", timed(jax.jit(step_scalar), (state, batch), args.iters),
         note="one full update: 2 unrolls + loss + priorities + Adam + sync select")

    # --- residual rows ---
    by = {r["component"]: r["ms"] for r in rows}
    emit("residual_opt_and_glue", by["train_step"]
         - (by["unroll_fwd_bwd"] + by["unroll_fwd"]),
         note="train_step minus (online fwd+bwd + target fwd): Adam, sync, "
              "loss/priority glue, un-fused overhead")
    emit("residual_unroll_vs_parts_fwd", by["unroll_fwd"]
         - (by["encoder_fwd"] + by["core_fwd"]),
         note="full-net fwd minus (encoder + core): heads + gathers + "
              "fusion gain(-)/loss(+)")

    if args.out:
        with open(args.out, "a") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
