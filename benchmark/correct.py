"""The comparisons that decide `correct`, outside every timed window.

What is compared is the same for every architecture; what an architecture IS
(its plain reference, its kernels, limits of its own) comes from the reference
module its configuration names (harness.reference_for). Four checks, each
returning a dict with `ok`, the numbers behind it and their limits:

1. kernel_checks: the reference module's own (`model`: the Pallas LSTM
   sequence kernel against the scan LSTM); a module without kernels has none.
2. ReferenceCheck: on a seeded sample of stored sequences, the program's
   learning-window Q values, loss and gradient norm against the module's plain
   float32 reference. The learn driver judges all of it on the parameters the
   window STARTS with (after the same number of updates whatever the
   program's speed); at the END state it judges Q alone (END_STATE), which is
   measured on the tensor's own scale, and records the loss and the gradient
   norm, whose relative errors grow as training shrinks them.
3. loss_island: the program's loss island ALONE on Q views the reference
   provides, so that no bfloat16 trunk hides it.
4. serve_vs_reference: Q returned for a session's requests through the server
   (cache + buckets) against the reference's full unroll of the same inputs.

Tolerances (TOL; a configuration file may state its own under `"limits"`).
The program computes conv and core matmuls in bfloat16 (unit round-off 2^-8 =
3.9e-3) and the heads, the loss and the stored state in float32; the reference
is float32 at "highest" matmul precision. Q and kernel errors are measured
relative to the tensor's own scale (max |reference|), as chip_smoke.py does.
Worst values of the sound program on the v5e in PRs 22-26 (PERF.md findings 7,
25.3, 26.2, 26.3): learning-window Q 8.2e-3 (T=581; 4.8e-3 at T=85), loss
4.4e-4 relative, gradient norm 2.5e-2 relative; kernel forward 6.5e-3, kernel
gradients 1.2e-2 relative L2; served Q after 32 requests 1.34e-2. The bf16
limits are 2 to 7 times those. float32 configurations are held to limits 50
to 100 times tighter.

What each limit separates (the control is the reference put in the program's
place one precision down; my chip runs, PR 26, PERF.md finding 26.3):
- T=581 (lru-seq581): `q` 1.5e-2 stands 1.8 x above the sound program's
  largest (8.2e-3) and 2.6 x below the all-bfloat16 reference's smallest
  (3.95e-2); float8 weights read 0.19 at least.
- T=85 (nature-lstm512 and -dp4): Q relative to its scale reads 1.1e-3 to
  4.8e-3 sound against 7.0e-3 to 2.6e-2 with float8 weights: the scale itself
  (0.05-0.15 at the start state) moves by seed. The largest ABSOLUTE error
  does not: 1.3e-4 to 2.7e-4 sound (2.3e-4 on the seed that reads 4.8e-3)
  against 1.0e-3 to 2.0e-3. So those two configuration files state `q_abs`
  6e-4 (judged at the start state only: Q grows with training) and `q` 1e-2.
- the loss and the gradient norm separate nothing a precision down (float8
  weights move them by 1e-5 to 2e-3 and 2e-5 to 7e-2 relative, the sound
  program by up to 4.4e-4 and 2.5e-2): they catch wrong mathematics (a
  weight, a seam, the rescaling), by orders of magnitude.
- heads, loss or stored state in bfloat16 are not told from the sound
  program by any of these at T=85 (the all-bfloat16 reference reads Q 3.4e-3
  to 7.5e-3): loss_island is for the loss; the heads and the stored state
  have no check (below).

Floors (`loss_floor`, `grad_norm_floor`, bfloat16 class). The loss and the
gradient norm are compared relative to the sampled batch's OWN value, and the
bf16 program's absolute error does not shrink with it: |loss - ref| <=
max(tol x |ref|, floor), the gradient norm likewise. Evidence: 1,358 batches
of 8 sequences at the window-start states of the three cells (14 runs; my
chip runs, PR 26) and PR 25's 2,400 at end states (PERF.md 25.3).
- loss_floor 5e-5 governs under a sampled loss of 0.0167. There the largest
  absolute error was 2.4e-6 (79 batches, lru); over all start-state batches
  7.6e-5 at a loss of 0.43 (relative 1.8e-4). PR 25's end states: medians
  3e-6 to 3e-5, and every batch that crossed the relative limit was a ~1e-5
  absolute error at a loss of 0.003-0.007 (the dp4 run that refused PR 24).
- grad_norm_floor 2e-3 governs under a norm of 0.033. The absolute error of
  the norm does not scale with the norm: largest 4.1e-4 at norms under 0.05
  (down to 0.008), 5.9e-4 over all (median 1e-4); the floor is 3.4 x that.

LOSS_ISLAND (63 start-state batches over 21 weight seeds, nature and lru, and
5 official runs, one of them dp4; my chip runs, PR 26): the program's island
and the reference's agree to the last bit (both errors 0 in all 68); the reference's
island computed in bfloat16 reads a loss 5.8e-5 to 5.6e-3 off (relative) and
dloss/dq 2.6e-3 to 7.3e-3 of its scale off, and the program with its island
cast to bfloat16 (a scratch copy) 3.7e-4 to 4.9e-4 and 3.2e-3, where the
whole-program numbers above pass it: limits 1e-5 and 1e-4.

The dueling heads have no check of their precision, because on the TPU there
is none to hold them to: a float32 matmul at the default precision multiplies
in bfloat16. On the reference's core outputs the program's heads and the
reference's at that precision agree to the bit, both 8e-4 to 6.8e-3 off the
"highest" reference; heads computed in bfloat16 read 3.6e-3 to 8.9e-3 and
heads whose output is rounded to bfloat16 2.5e-3 to 7.2e-3: no limit fits
between. Inside a program XLA elides a float32 -> bfloat16 -> float32 round
trip altogether (the scratch copy's rounded heads emitted float32 values).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

TOL = {
    # compute dtype -> limits
    "bfloat16": {"q": 1.5e-2, "loss": 3e-3, "grad_norm": 6e-2, "kernel_fwd": 2.5e-2,
                 "kernel_grad_l2": 4e-2, "serve_q": 5e-2,
                 "loss_floor": 5e-5, "grad_norm_floor": 2e-3},
    # no cell computes in float32, so no chip run has read what a floor would
    # have to cover: its two relative limits stand alone until one does
    "float32": {"q": 2.5e-4, "loss": 5e-4, "grad_norm": 8e-4, "kernel_fwd": 1e-4,
                "kernel_grad_l2": 1e-4, "serve_q": 2.5e-4,
                "loss_floor": 0.0, "grad_norm_floor": 0.0},
}


# The loss island alone, whatever the compute dtype (loss_island below)
LOSS_ISLAND = {"loss_rel": 1e-5, "dq_err_over_scale": 1e-4}


def tolerances(ref, cfg, config: Optional[dict] = None) -> Dict[str, float]:
    """The limits for this configuration: the reference module's own TOL where
    the architecture needs one, else the table above, then the limits the
    configuration's file states for itself (`"limits"`: the start state's
    readings differ by sequence length, see the header)."""
    limits = dict(getattr(ref, "TOL", TOL)[cfg.resolved_compute_dtype])
    own = (config or {}).get("limits", {})
    unknown = sorted(set(own) - set(limits) - {"q_abs"})
    if unknown:
        raise KeyError(f"the configuration's limits name {unknown}; the table has {sorted(limits)} and q_abs")
    return {**limits, **own}


def scale_err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6))


def _rel(got, want) -> float:
    return float(abs(float(got) - float(want)) / (abs(float(want)) + 1e-12))


def kernel_checks(ref, cfg, seed: int, batch: int) -> Dict:
    check = getattr(ref, "kernel_checks", None)
    if check is None:
        return {"ok": True, "skipped": f"reference module {ref.__name__} names no kernel"}
    return check(cfg, seed, batch)


def reference_batch(batch) -> Dict:
    """A learner.DeviceBatch as the dict of arrays a reference module takes."""
    return {
        "obs": batch.obs, "last_action": batch.last_action, "last_reward": batch.last_reward,
        "hidden": batch.hidden, "action": batch.action, "n_step_reward": batch.n_step_reward,
        "gamma": batch.gamma, "burn_in": batch.burn_in_steps, "learning": batch.learning_steps,
        "forward": batch.forward_steps, "is_weights": batch.is_weights,
    }


END_STATE = ("q_err_over_scale",)


def reference_verdict(loss, loss_ref, grad_norm, grad_norm_ref, q_err, tol,
                      judged: Optional[Sequence[str]] = None, q_abs_err: float = 0.0) -> Dict:
    """The numbers of the reference check beside their limits. Q is on the
    tensor's own scale (and, where the configuration states `q_abs`, also as
    the largest absolute error); the loss and the gradient norm are held to
    the larger of their relative limit and their absolute floor. `ok` is taken
    over the numbers named in `judged` (default: every limit; the end state:
    END_STATE)."""
    loss, loss_ref = float(loss), float(loss_ref)
    grad_norm, grad_norm_ref = float(grad_norm), float(grad_norm_ref)
    out = {
        "q_err_over_scale": float(q_err), "q_abs_err": float(q_abs_err),
        "loss_rel": _rel(loss, loss_ref), "grad_norm_rel": _rel(grad_norm, grad_norm_ref),
        "loss_abs_err": abs(loss - loss_ref), "grad_norm_abs_err": abs(grad_norm - grad_norm_ref),
        "loss": loss, "loss_ref": loss_ref, "grad_norm": grad_norm, "grad_norm_ref": grad_norm_ref,
        "limits": {
            "q_err_over_scale": tol["q"],
            "loss_abs_err": max(tol["loss"] * abs(loss_ref), tol["loss_floor"]),
            "grad_norm_abs_err": max(tol["grad_norm"] * abs(grad_norm_ref), tol["grad_norm_floor"]),
        },
    }
    if "q_abs" in tol:
        out["limits"]["q_abs_err"] = tol["q_abs"]
    out["judged"] = list(out["limits"] if judged is None else judged)
    out["ok"] = bool(np.isfinite(loss) and np.isfinite(grad_norm)
                     and all(out[k] <= out["limits"][k] for k in out["judged"]))
    return out


class ReferenceCheck:
    """The program's learning-window Q, loss and gradient norm on a few stored
    sequences against the plain reference, as two jitted functions that are
    compiled once and asked at as many operating points as the driver likes
    (the float32 reference at T=581 is ~200 s of backend compile cold).
    Program: make_loss_fn through the net as built (kernels, compute dtype).
    Reference: `ref.loss_q_gradnorm`, float32 at highest precision."""

    def __init__(self, ref, cfg, net, config: Optional[dict] = None):
        import jax
        import jax.numpy as jnp
        import optax

        from r2d2_tpu.learner import make_loss_fn

        loss_fn = make_loss_fn(cfg, net)
        sizes = ref.sizes_of(cfg)

        def program(params, target_params, b):
            denom = jnp.maximum(jnp.sum(b.learning_steps).astype(jnp.float32), 1.0)
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, target_params, b, denom)
            q_learn, _, _ = net.apply(params, b.obs, b.last_action, b.last_reward, b.hidden,
                                      b.burn_in_steps, b.learning_steps, b.forward_steps, b.task)
            return loss, q_learn, optax.global_norm(grads)

        self._program = jax.jit(program)
        self._reference = jax.jit(lambda p, tp, b: ref.loss_q_gradnorm(p, tp, b, sizes))
        self.tol = tolerances(ref, cfg, config)

    def __call__(self, params, target_params, batch, judged: Optional[Sequence[str]] = None) -> Dict:
        """`params` / `target_params`: the flax variable trees; `batch`: a
        learner.DeviceBatch of a few stored sequences (host or single-device
        arrays)."""
        import jax

        got = self._program(params, target_params, batch)
        with jax.default_matmul_precision("highest"):
            want = self._reference(params["params"], target_params["params"], reference_batch(batch))
        got, want = jax.device_get(got), jax.device_get(want)
        out = reference_verdict(got[0], want[0], got[2], want[2], scale_err(got[1], want[1]), self.tol,
                                judged, q_abs_err=float(np.max(np.abs(got[1] - want[1]))))
        out["q_scale"] = float(np.max(np.abs(want[1])))
        out["sequences"] = int(np.asarray(batch.obs).shape[0])
        return out


class _GivenQ:
    """Stands where make_loss_fn expects the network: `apply` returns the Q
    views it was given as `params`, so that the loss function's own island is
    all that runs. (make_loss_fn keeps its island in a closure; this stand-in
    is how the benchmark reaches it without a change to the program.)"""

    @staticmethod
    def apply(given, obs, last_action, last_reward, hidden, burn_in, learning, forward, task=None):
        return given["q_learn"], given["q_boot"], given["mask"]


def loss_island(ref, cfg, params, target_params, batch) -> Dict:
    """The program's loss island ALONE: make_loss_fn fed the three Q views the
    reference provides (`ref.island_inputs`), its loss and dloss/dq against
    `ref.loss_from_q` of the same views. No bfloat16 trunk stands between the
    island, which every configuration states in float32, and its limits
    (LOSS_ISLAND; PERF.md finding 26.3). A reference module without
    `island_inputs` has no such check."""
    import jax
    import jax.numpy as jnp

    from r2d2_tpu.learner import make_loss_fn

    if not callable(getattr(ref, "island_inputs", None)):
        return {"ok": True, "skipped": f"reference module {ref.__name__} provides no island inputs"}
    loss_fn = make_loss_fn(cfg, _GivenQ)
    sizes = ref.sizes_of(cfg)
    rb = reference_batch(batch)

    def program(views, b):
        denom = jnp.maximum(jnp.sum(b.learning_steps).astype(jnp.float32), 1.0)
        target = dict(views, q_boot=views["q_boot_target"])
        return jax.value_and_grad(lambda q: loss_fn(dict(views, q_learn=q), target, b, denom)[0])(views["q_learn"])

    def reference(views, rb):
        return jax.value_and_grad(lambda q: ref.loss_from_q(
            q, views["q_boot"], views["q_boot_target"], views["mask"], rb, sizes))(views["q_learn"])

    with jax.default_matmul_precision("highest"):
        views = jax.jit(lambda p, tp, rb: ref.island_inputs(p, tp, rb, sizes))(
            params["params"], target_params["params"], rb)
        want = jax.device_get(jax.jit(reference)(views, rb))
    got = jax.device_get(jax.jit(program)(views, batch))
    out = {"loss_rel": _rel(got[0], want[0]), "dq_err_over_scale": scale_err(got[1], want[1]),
           "limits": dict(LOSS_ISLAND)}
    out["ok"] = bool(all(np.isfinite(out[k]) and out[k] <= limit for k, limit in out["limits"].items()))
    return out


def serve_vs_reference(ref, cfg, params, obs, actions, rewards, q_served) -> Dict:
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
    sz = ref.sizes_of(cfg)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, o, a, r: ref.act_unroll(p, o, a, r, sz))(
            params["params"], jnp.asarray(obs), jnp.asarray(last_action), jnp.asarray(last_reward))
    err_last = scale_err(q_served[:, -1], np.asarray(want)[:, -1])
    err_all = scale_err(q_served, want)
    tol = tolerances(ref, cfg)["serve_q"]
    return {"ok": bool(np.isfinite(q_served).all() and max(err_last, err_all) <= tol),
            "q_err_over_scale_last": err_last, "q_err_over_scale_all": err_all,
            "limits": {"q_err_over_scale_last": tol, "q_err_over_scale_all": tol},
            "sessions": int(S), "steps": int(T)}
