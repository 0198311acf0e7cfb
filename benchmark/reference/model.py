"""Plain float32 reference of the R2D2 agent: forward, loss and gradients.

Straightforward `jax.numpy`, no kernels, no replay, no batching tricks, written
from the published description and independent of r2d2_tpu's model code (it
shares only the parameter tree's names, so the same seeded weights feed both):

- Kapturowski et al., "Recurrent Experience Replay in Distributed
  Reinforcement Learning" (ICLR 2019): Nature-DQN conv trunk -> LSTM over
  concat(latent, one-hot last action, last reward) -> dueling heads; stored
  state + burn-in; double-Q n-step targets under the invertible value
  rescaling h(x) = sign(x)(sqrt(|x|+1)-1) + eps*x; loss = importance-weighted
  squared TD error averaged over the valid learning steps (SURVEY 2.6).
- Orvieto et al., "Resurrecting Recurrent Neural Networks for Long
  Sequences" (2023) for the LRU core: h_t = lambda*h_{t-1} + gamma*(B x_t),
  y_t = gelu(Re(h_t C)) + D x_t, run here as a plain sequential scan.

Every caller wraps these in `jax.default_matmul_precision("highest")`: on a
TPU a float32 matmul otherwise runs as a bf16 pass.

Departures from the papers, each because the program does the same and the
comparison is with the program:
- burn-in: the LSTM reference cuts the gradient at each row's burn-in seam
  (the paper's burn-in produces a start state only). The LRU reference does
  NOT: the program backpropagates through burn-in for that core
  (models/r2d2.py), so the reference follows it; PERF.md lists this.
- the bootstrap index is clamped at the end of the stored sequence
  (edge-repeat), as the reference implementation pads it.
- gelu is the tanh approximation (flax's default).

This module is the reference `model` (harness.reference_for): what the
benchmark knows about the architectures {nature, mlp} x {lstm, lru}. Besides
the forward, loss and gradients it holds their operation count (update_flops,
through flops.py) and the check of their one kernel (kernel_checks: the
Pallas LSTM against the scan LSTM). Another architecture is another file here.
"""

from __future__ import annotations

import contextlib
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import correct, flops

F32 = jnp.float32


class Sizes(NamedTuple):
    """The shape facts the reference needs (read off the run's config)."""

    encoder: str          # "nature" | "mlp"
    core: str             # "lstm" | "lru"
    hidden: int
    action_dim: int
    learning: int
    forward: int
    eps: float = 1e-3     # value-rescale epsilon


def sizes_of(cfg) -> Sizes:
    return Sizes(
        encoder=cfg.encoder, core=cfg.recurrent_core, hidden=cfg.hidden_dim,
        action_dim=cfg.action_dim, learning=cfg.learning_steps,
        forward=cfg.forward_steps, eps=cfg.value_rescale_eps,
    )


def update_flops(cfg) -> int:
    """Operations one learner update requires (flops.update_flops)."""
    return flops.update_flops(
        encoder=cfg.encoder, obs_shape=cfg.obs_shape, hidden=cfg.hidden_dim,
        action_dim=cfg.action_dim, core=cfg.recurrent_core, lru_chunk=cfg.lru_chunk,
        batch=cfg.batch_size, burn_in=cfg.burn_in_steps, learning=cfg.learning_steps,
        forward=cfg.forward_steps,
    )


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, F32), tree)


def _dense(p, x):
    return x @ p["kernel"] + p["bias"]


def encode(p_enc: Dict, obs: jnp.ndarray, encoder: str) -> jnp.ndarray:
    """(N, H, W, C) uint8 -> (N, latent). Pixels are scaled to [0, 1] once."""
    x = obs.astype(F32) / 255.0
    if encoder == "nature":
        for i, stride in enumerate((4, 2, 1)):
            p = p_enc[f"Conv_{i}"]
            x = jax.lax.conv_general_dilated(
                x, p["kernel"], (stride, stride), "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            ) + p["bias"]
            x = jnp.maximum(x, 0.0)
    elif encoder != "mlp":
        raise KeyError(f"no reference for encoder {encoder!r}")
    x = x.reshape(x.shape[0], -1)
    return jnp.maximum(_dense(p_enc["Dense_0"], x), 0.0)


def core_input(p, obs, last_action, last_reward, sz: Sizes):
    latent = encode(p["enc"], obs, sz.encoder)
    onehot = jax.nn.one_hot(last_action, sz.action_dim, dtype=F32)
    return jnp.concatenate([latent, onehot, last_reward.astype(F32)[:, None]], axis=-1)


def lstm_step(pc, x, h, c):
    H = h.shape[-1]
    z = x @ pc["wi"] + pc["b"] + h @ pc["wh"]
    i = jax.nn.sigmoid(z[:, :H])
    f = jax.nn.sigmoid(z[:, H:2 * H])
    g = jnp.tanh(z[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(z[:, 3 * H:])
    c = f * c + i * g
    h = o * jnp.tanh(c)
    return h, (h, c)


def _lru_consts(pc):
    mod = jnp.exp(-jnp.exp(pc["nu_log"]))
    theta = jnp.exp(pc["theta_log"])
    gamma = jnp.sqrt(jnp.maximum(1.0 - mod * mod, 1e-8))
    return mod * jnp.cos(theta), mod * jnp.sin(theta), gamma


def lru_step(pc, x, re, im):
    lam_re, lam_im, gamma = _lru_consts(pc)
    u_re = (x @ pc["in_re"]) * gamma
    u_im = (x @ pc["in_im"]) * gamma
    re, im = lam_re * re - lam_im * im + u_re, lam_re * im + lam_im * re + u_im
    y = jax.nn.gelu(re @ pc["out_re"] - im @ pc["out_im"], approximate=True) + x @ pc["skip"]
    return y, (re, im)


def core_step(pc, x, carry, core: str):
    if core == "lstm":
        return lstm_step(pc, x, *carry)
    if core == "lru":
        return lru_step(pc, x, *carry)
    raise KeyError(f"no reference for core {core!r}")


def dueling(p, h):
    adv = _dense(p["adv_out"], jnp.maximum(_dense(p["adv_hidden"], h), 0.0))
    val = _dense(p["val_out"], jnp.maximum(_dense(p["val_hidden"], h), 0.0))
    return val + adv - adv.mean(axis=-1, keepdims=True)


def unroll_outputs(p, obs, last_action, last_reward, hidden, burn_in, sz: Sizes):
    """Core outputs (B, T, H) from the stored state, one step at a time."""
    B, T = obs.shape[:2]
    x = core_input(
        p, obs.reshape(B * T, *obs.shape[2:]), last_action.reshape(-1),
        last_reward.reshape(-1), sz,
    ).reshape(B, T, -1)
    carry = (hidden[:, 0].astype(F32), hidden[:, 1].astype(F32))

    def step(carry, inp):
        t, x_t = inp
        if sz.core == "lstm":
            # burn-in only refreshes the state: cut the gradient at the seam
            seam = (t == burn_in)[:, None]
            carry = tuple(jnp.where(seam, jax.lax.stop_gradient(a), a) for a in carry)
        out, carry = core_step(p["core"], x_t, carry, sz.core)
        return carry, out

    _, outs = jax.lax.scan(step, carry, (jnp.arange(T), jnp.swapaxes(x, 0, 1)))
    return jnp.swapaxes(outs, 0, 1)


def q_views(p, batch, sz: Sizes):
    """(q_learn, q_boot, mask): Q at the learning steps, Q at the n-step
    bootstrap positions (clamped at the stored end), validity mask."""
    L, F = sz.learning, sz.forward
    obs = batch["obs"]
    T = obs.shape[1]
    burn, learn, fwd = batch["burn_in"], batch["learning"], batch["forward"]
    outs = unroll_outputs(
        p, obs, batch["last_action"], batch["last_reward"], batch["hidden"], burn, sz
    )
    t = jnp.arange(L)
    learn_idx = jnp.clip(burn[:, None] + t[None], 0, T - 1)
    end = (burn + learn + fwd)[:, None] - 1
    boot_idx = jnp.clip(jnp.minimum(burn[:, None] + F + t[None], end), 0, T - 1)
    take = lambda idx: jnp.take_along_axis(outs, idx[:, :, None], axis=1)
    mask = (t[None] < learn[:, None]).astype(F32)
    return dueling(p, take(learn_idx)), dueling(p, take(boot_idx)), mask


def value_rescale(x, eps):
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + eps * x


def inverse_value_rescale(x, eps):
    t = (jnp.sqrt(1.0 + 4.0 * eps * (jnp.abs(x) + 1.0 + eps)) - 1.0) / (2.0 * eps)
    return jnp.sign(x) * (t * t - 1.0)


def loss_from_q(q_learn, q_boot, q_boot_target, mask, batch, sz: Sizes):
    """The loss island alone: double-Q n-step target under the value
    rescaling, importance-weighted squared TD error over the valid steps."""
    a_star = jnp.argmax(jax.lax.stop_gradient(q_boot), axis=-1)
    q_next = jnp.take_along_axis(q_boot_target, a_star[..., None], axis=-1)[..., 0]
    y = value_rescale(
        batch["n_step_reward"] + batch["gamma"] * inverse_value_rescale(q_next, sz.eps),
        sz.eps,
    )
    y = jax.lax.stop_gradient(y)
    q_taken = jnp.take_along_axis(q_learn, batch["action"][..., None], axis=-1)[..., 0]
    td = y - q_taken
    w = batch["is_weights"][:, None]
    return jnp.sum(w * td * td * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def loss_and_q(params, target_params, batch, sz: Sizes):
    """-> (loss, q_learn). `params` are the flax trees' inner "params" dicts."""
    q_learn, q_boot, mask = q_views(params, batch, sz)
    _, q_boot_target, _ = q_views(target_params, batch, sz)
    batch = dict(batch, is_weights=batch["is_weights"].astype(F32))
    return loss_from_q(q_learn, q_boot, q_boot_target, mask, batch, sz), q_learn


def loss_q_gradnorm(params, target_params, batch, sz: Sizes):
    """-> (loss, q_learn (B, L, A), global gradient norm), all float32."""
    params, target_params = _f32(params), _f32(target_params)
    (loss, q_learn), grads = jax.value_and_grad(loss_and_q, has_aux=True)(
        params, target_params, batch, sz
    )
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return loss, q_learn, gnorm


def island_inputs(params, target_params, batch, sz: Sizes) -> Dict:
    """What the loss island reads, made by the reference alone: the three Q
    views and the validity mask."""
    params, target_params = _f32(params), _f32(target_params)
    q_learn, q_boot, mask = q_views(params, batch, sz)
    return {"q_learn": q_learn, "q_boot": q_boot, "mask": mask,
            "q_boot_target": q_views(target_params, batch, sz)[1]}


def act_unroll(params, obs, last_action, last_reward, sz: Sizes) -> jnp.ndarray:
    """Acting from the zero state: obs (S, T, ...), last_action/last_reward
    (S, T) -> Q (S, T, A). What a session's T requests must return."""
    params = _f32(params)
    S, T = obs.shape[:2]
    x = core_input(
        params, obs.reshape(S * T, *obs.shape[2:]), last_action.reshape(-1),
        last_reward.reshape(-1), sz,
    ).reshape(S, T, -1)
    zero = jnp.zeros((S, sz.hidden), F32)

    def step(carry, x_t):
        out, carry = core_step(params["core"], x_t, carry, sz.core)
        return carry, out

    _, outs = jax.lax.scan(step, (zero, zero), jnp.swapaxes(x, 0, 1))
    return dueling(params, jnp.swapaxes(outs, 0, 1))


def kernel_checks(cfg, seed: int, batch: int) -> Dict:
    """The Pallas LSTM sequence kernel, forward and default backward arm,
    against the lax.scan LSTM at (cfg.seq_len, batch, cfg.hidden_dim) and the
    compute dtype, on seeded inputs: chip_smoke.py's kernel phase, cut to the
    two programs the cells run. Skipped (ok, with the reason) where the
    configuration's core is not that kernel."""
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
    fwd_err = correct.scale_err(fwd["pallas"], fwd["scan"])
    l2 = max(
        float(np.linalg.norm(np.asarray(a, np.float32) - np.asarray(r, np.float32))
              / (np.linalg.norm(np.asarray(r, np.float32)) + 1e-6))
        for a, r in zip(jax.tree.leaves(grad["pallas"]), jax.tree.leaves(grad["scan"]))
    )
    tol = correct.TOL[dtype.name]
    finite = bool(np.isfinite(np.asarray(fwd["pallas"], np.float32)).all())
    return {"ok": finite and fwd_err <= tol["kernel_fwd"] and l2 <= tol["kernel_grad_l2"],
            "fwd_err_over_scale": fwd_err, "grad_rel_l2": l2, "tbh": [T, B, H],
            "limits": {"fwd_err_over_scale": tol["kernel_fwd"], "grad_rel_l2": tol["kernel_grad_l2"]}}
