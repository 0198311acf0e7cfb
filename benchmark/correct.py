"""The comparisons that decide `correct`, outside every timed window.

Three checks, each returning a dict with `ok` and the numbers behind it:

1. kernels_vs_scan: the Pallas LSTM sequence kernel (forward and the default
   backward arm) against the lax.scan LSTM at the cell's own (T, B, H) and
   compute dtype, on seeded inputs — chip_smoke.py's kernel phase, cut to the
   two programs the cells run. Skipped (ok, with the reason) where the
   configuration's core is not the Pallas kernel.
2. system_vs_reference: on a seeded sample of stored sequences, the program's
   learning-window Q values, loss and gradient norm against the plain float32
   reference (reference/model.py).
3. serve_vs_reference: Q returned for a session's requests through the server
   (cache + buckets) against the reference's full unroll of the same inputs.

Tolerances. The program computes conv and core matmuls in bfloat16 (8
mantissa bits, unit round-off 2^-9 = 2.0e-3) and the heads, the loss and the
stored state in float32; the reference is float32 at "highest" matmul
precision. Errors are measured relative to the tensor's own scale (max
|reference|), as chip_smoke.py does. Worst values measured on the v5e in PR 22
over all runs of all cells (PERF.md section 6): learning-window Q 2.8e-3 (T=581;
7.8e-4 at T=85), loss 3.2e-4, gradient norm 1.3e-2; kernel forward 6.0e-3,
kernel gradients 1.14e-2 relative L2; served Q after 32 requests 1.34e-2. The
bf16 limits below are 4 to 9 times those. The loss limit is the sharp one: a TD
error is a small difference of two O(1) numbers, so bf16 heads or bf16 loss
math (each >= 2^-9 on y and on Q) moves it by ~1e-2 relative and fails;
float32 configurations are held to limits 50 to 100 times tighter.
"""

from __future__ import annotations

import contextlib
from typing import Dict

import numpy as np

from benchmark.reference import model as ref

TOL = {
    # compute dtype -> limits
    "bfloat16": {"q": 1.5e-2, "loss": 3e-3, "grad_norm": 6e-2, "kernel_fwd": 2.5e-2,
                 "kernel_grad_l2": 4e-2, "serve_q": 5e-2},
    "float32": {"q": 2.5e-4, "loss": 5e-4, "grad_norm": 8e-4, "kernel_fwd": 1e-4,
                "kernel_grad_l2": 1e-4, "serve_q": 2.5e-4},
}


def sizes_of(cfg) -> ref.Sizes:
    return ref.Sizes(
        encoder=cfg.encoder, core=cfg.recurrent_core, hidden=cfg.hidden_dim,
        action_dim=cfg.action_dim, learning=cfg.learning_steps,
        forward=cfg.forward_steps, eps=cfg.value_rescale_eps,
    )


def _scale_err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6))


def _rel(got, want) -> float:
    return float(abs(float(got) - float(want)) / (abs(float(want)) + 1e-12))


def kernels_vs_scan(cfg, seed: int, batch: int) -> Dict:
    """Forward and default-arm backward of the Pallas sequence kernel vs the
    scan LSTM at (cfg.seq_len, batch, cfg.hidden_dim), compute dtype."""
    import jax
    import jax.numpy as jnp

    if cfg.resolved_core_backend != "pallas":
        return {"ok": True, "skipped": f"core is {cfg.resolved_core_backend}, not the Pallas kernel"}
    from r2d2_tpu.models.lstm import LSTM

    T, B, H = cfg.seq_len, batch, cfg.hidden_dim
    D = H + cfg.action_dim + 1
    dtype = jnp.dtype(cfg.resolved_compute_dtype)
    fp32 = dtype == jnp.float32
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
    carry = tuple(jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * 0.2) for _ in range(2))
    # the seams collect.py emits: 0 (first window of a block) or the full burn-in
    burn = jnp.asarray(np.where(np.arange(B) % 4 == 0, 0, cfg.burn_in_steps).astype(np.int32))
    scan_mod = LSTM(hidden_dim=H, in_dim=D, dtype=dtype, backend="scan")
    pal_mod = LSTM(hidden_dim=H, in_dim=D, dtype=dtype, backend="pallas")
    params = scan_mod.init(jax.random.PRNGKey(seed), xs, carry)

    def loss(mod, p):
        outs, _ = mod.apply(p, xs, carry, burn_in=burn)
        return jnp.sum(jnp.tanh(outs.astype(jnp.float32)))

    # fp32 parity needs true f32 matmuls on both sides; bf16 runs as production
    # does (a bf16 kernel under "highest" is refused by Mosaic, PERF.md 6)
    ctx = jax.default_matmul_precision("highest") if fp32 else contextlib.nullcontext()
    with ctx:
        fwd = {n: jax.jit(lambda p, m=m: m.apply(p, xs, carry, burn_in=burn)[0])(params)
               for n, m in (("scan", scan_mod), ("pallas", pal_mod))}
        grad = {n: jax.jit(jax.grad(lambda p, m=m: loss(m, p)))(params)
                for n, m in (("scan", scan_mod), ("pallas", pal_mod))}
    fwd_err = _scale_err(fwd["pallas"], fwd["scan"])
    l2 = max(
        float(np.linalg.norm(np.asarray(a, np.float32) - np.asarray(r, np.float32))
              / (np.linalg.norm(np.asarray(r, np.float32)) + 1e-6))
        for a, r in zip(jax.tree.leaves(grad["pallas"]), jax.tree.leaves(grad["scan"]))
    )
    tol = TOL[dtype.name]
    finite = bool(np.isfinite(np.asarray(fwd["pallas"], np.float32)).all())
    return {"ok": finite and fwd_err <= tol["kernel_fwd"] and l2 <= tol["kernel_grad_l2"],
            "fwd_err_over_scale": fwd_err, "grad_rel_l2": l2, "tbh": [T, B, H]}


def system_vs_reference(cfg, net, state, batch) -> Dict:
    """`batch` is a learner.DeviceBatch of a few stored sequences (host or
    single-device arrays). Program: make_loss_fn through the net as built
    (kernels, compute dtype). Reference: float32 at highest precision."""
    import jax
    import jax.numpy as jnp
    import optax

    from r2d2_tpu.learner import make_loss_fn

    loss_fn = make_loss_fn(cfg, net)

    def program(params, target_params, b):
        denom = jnp.maximum(jnp.sum(b.learning_steps).astype(jnp.float32), 1.0)
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, target_params, b, denom)
        q_learn, _, _ = net.apply(params, b.obs, b.last_action, b.last_reward, b.hidden,
                                  b.burn_in_steps, b.learning_steps, b.forward_steps, b.task)
        return loss, q_learn, optax.global_norm(grads)

    got = jax.jit(program)(state.params, state.target_params, batch)
    rb = {
        "obs": batch.obs, "last_action": batch.last_action, "last_reward": batch.last_reward,
        "hidden": batch.hidden, "action": batch.action, "n_step_reward": batch.n_step_reward,
        "gamma": batch.gamma, "burn_in": batch.burn_in_steps, "learning": batch.learning_steps,
        "forward": batch.forward_steps, "is_weights": batch.is_weights,
    }
    sz = sizes_of(cfg)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, tp, b: ref.loss_q_gradnorm(p, tp, b, sz))(
            state.params["params"], state.target_params["params"], rb)
    got, want = jax.device_get(got), jax.device_get(want)
    tol = TOL[cfg.resolved_compute_dtype]
    out = {
        "q_err_over_scale": _scale_err(got[1], want[1]),
        "loss_rel": _rel(got[0], want[0]),
        "grad_norm_rel": _rel(got[2], want[2]),
        "loss": float(got[0]), "loss_ref": float(want[0]),
        "sequences": int(np.asarray(batch.obs).shape[0]),
    }
    out["ok"] = bool(
        np.isfinite(got[0]) and out["q_err_over_scale"] <= tol["q"]
        and out["loss_rel"] <= tol["loss"] and out["grad_norm_rel"] <= tol["grad_norm"]
    )
    return out


def serve_vs_reference(cfg, params, obs, actions, rewards, q_served) -> Dict:
    """obs (S, T, ...) uint8 as submitted, actions (S, T) as the server
    returned them, rewards (S, T) as submitted, q_served (S, T, A). The
    reference is teacher-forced with the served actions (the last action is an
    input of the next step), so only Q is compared, never an argmax."""
    import jax
    import jax.numpy as jnp

    S, T = actions.shape
    last_action = np.concatenate([np.zeros((S, 1), np.int32), actions[:, :-1]], axis=1)
    # a reset request zeroes the reward input; later ones carry the request's
    last_reward = np.concatenate([np.zeros((S, 1), np.float32), rewards[:, 1:]], axis=1)
    sz = sizes_of(cfg)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, o, a, r: ref.act_unroll(p, o, a, r, sz))(
            params["params"], jnp.asarray(obs), jnp.asarray(last_action), jnp.asarray(last_reward))
    err_last = _scale_err(q_served[:, -1], np.asarray(want)[:, -1])
    err_all = _scale_err(q_served, want)
    tol = TOL[cfg.resolved_compute_dtype]["serve_q"]
    return {"ok": bool(np.isfinite(q_served).all() and max(err_last, err_all) <= tol),
            "q_err_over_scale_last": err_last, "q_err_over_scale_all": err_all,
            "sessions": int(S), "steps": int(T)}
