"""The tail of `R2D2Network.unroll` and the loss alone, on the chip, as one
update of each benchmark cell runs them (ISSUE 46's step 0):

    python runs/unroll_tail_microbench.py                 # the three cells' shapes, bf16
    python runs/unroll_tail_microbench.py --cells lru --hlo-dir chiprun_out/tail_hlo

It STARTS at the producer: the core's outputs as the core emits them, TIME-major
`bf16[T, B, 512]` (the Pallas LSTM's and the LRU read-out's layout; both cores
end in `swapaxes(outs_t, 0, 1)`, so every form begins there), for every update
one array for the online net and one for the target. It ends at the double-Q TD loss, the
priorities, and the gradients w.r.t. the online `outs_t` and the dueling heads'
parameters. K updates to a call under `lax.scan`, each with `burn_in`,
`learning`, `forward` and actions of its own, as the step programs run them.
The forms of "Q at each row's learning and bootstrap positions":

  indexed         three `take_along_axis` of B x L rows (online both views,
                  target the bootstrap), heads over 2 L + L rows, the loss picks
                  Q by action with `take_along_axis` (the program until PR 46)
  indexed_select  the same gathers; the loss picks by `learner._q_at` (a select
                  over A): what the loss alone is worth
  slice           one window of W = L + F steps a row by `dynamic_slice` under
                  `vmap` (a loop of B slices on the chip), heads once over W
                  rows, the views cut from the window's Q: a static slice, and
                  the slice from F on with its tail held at the last valid step
  unrolled        the same window as B `dynamic_slice`s written out and stacked
  band            the same window by a selection matmul, `band[b, j, t] @
                  outs[b, t, h]` with one 1.0 a row (exact for bf16 `outs`):
                  `R2D2Network._dueling_window` since PR 46, called as it is
  head_all        the heads over all T positions, the window taken on Q with T
                  on the lanes by a barrel shifter of selects, as
                  `learner._windows`

One JSON line per reading: host clock around `--reps` calls in flight, per
UPDATE (a call is K of them), median of 5 rounds (never one blocking call:
PERF.md finding 34.2), with each form's largest |Q| difference from `indexed`
(0 for every form that moves the same rows into the same head; `head_all` runs
the head at another row count, so it may differ in the last bit); then one line
with each form's distance from `indexed`. A microbenchmark, not a cell: its
numbers rank the forms and are recorded in PERF.md as such. Exits 3 without a
TPU."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# cell -> (B rows per device, T, learning L, n-step F, K updates per dispatch)
CELLS = {
    "nature": (64, 85, 40, 5, 16),
    "lru": (32, 581, 512, 5, 4),
    "dp4": (16, 85, 40, 5, 16),
}
TINY = {"nature": (4, 13, 6, 3, 2), "lru": (2, 21, 16, 3, 2), "dp4": (1, 13, 6, 3, 2)}
FORMS = ("indexed", "indexed_select", "slice", "unrolled", "band", "head_all")
HIDDEN, TINY_HIDDEN, ACTIONS = 512, 32, 3


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--cells", nargs="*", default=list(CELLS), choices=list(CELLS))
    p.add_argument("--forms", nargs="*", default=list(FORMS), choices=list(FORMS))
    p.add_argument("--reps", type=int, default=30)
    p.add_argument("--hlo-dir", default=None, help="write each form's compiled text here")
    p.add_argument("--allow-cpu", action="store_true", help="run tiny on the CPU (a smoke test, no reading)")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from r2d2_tpu.learner import _q_at
    from r2d2_tpu.models.lstm import LSTM
    from r2d2_tpu.models.r2d2 import R2D2Network
    from r2d2_tpu.ops.priority import mixed_td_priorities
    from r2d2_tpu.ops.value_rescale import inverse_value_rescale, value_rescale

    if jax.default_backend() != "tpu" and not args.allow_cpu:
        print("no TPU: a microbenchmark of the chip's compiler has nothing to say here", file=sys.stderr)
        return 3
    device = jax.devices()[0].device_kind
    dtype = jnp.bfloat16
    H = TINY_HIDDEN if args.allow_cpu else HIDDEN

    def network(L, F):
        """The program's own module at the cell's window: only its heads and
        `_dueling_window` run here (the encoder and the core, which setup
        declares, make nothing until they are called)."""
        return R2D2Network(
            action_dim=ACTIONS, core=LSTM(H, in_dim=H + ACTIONS + 1), hidden_dim=H,
            learning_steps=L, forward_steps=F, encoder="mlp", compute_dtype="bfloat16")

    def views(form, net, params, outs_t, burn_in, learning, forward):
        """-> (q_learn, q_boot), each (B, L, A) f32, by one form."""
        head = lambda h: net.apply(params, h, method="_dueling")
        outs = jnp.swapaxes(outs_t, 0, 1)  # (B, T, H): where both cores end
        B, T, _ = outs.shape
        L, F = net.learning_steps, net.forward_steps
        W = L + F
        if form == "band":
            return net.apply(params, outs, burn_in, learning, forward, method="_dueling_window")
        if form in ("indexed", "indexed_select"):
            t = jnp.arange(L, dtype=jnp.int32)
            learn_idx = jnp.clip(burn_in[:, None] + t[None, :], 0, T - 1)
            boot_idx = jnp.minimum(burn_in[:, None] + F + t[None, :], (burn_in + learning + forward)[:, None] - 1)
            boot_idx = jnp.clip(boot_idx, 0, T - 1)
            return (head(jnp.take_along_axis(outs, learn_idx[:, :, None], axis=1)),
                    head(jnp.take_along_axis(outs, boot_idx[:, :, None], axis=1)))
        j = jnp.arange(W, dtype=jnp.int32)
        if form == "slice":
            q = head(jax.vmap(lambda row, s: jax.lax.dynamic_slice(row, (s, 0), (W, row.shape[1])))(outs, burn_in))
        elif form == "unrolled":
            q = head(jnp.stack([
                jax.lax.dynamic_slice(outs[b], (burn_in[b], 0), (W, outs.shape[2])) for b in range(B)]))
        else:  # head_all: Q at every position as (B, A, T), then `learner._windows`' shifter
            q = jnp.swapaxes(head(outs), 1, 2)
            q = jnp.concatenate([q, jnp.broadcast_to(q[..., -1:], (*q.shape[:2], W - 1))], axis=-1)
            for k in range((T - 1).bit_length()):
                q = jnp.where(((burn_in >> k) & 1 == 1)[:, None, None], jnp.roll(q, -(1 << k), axis=-1), q)
            q = jnp.swapaxes(q[..., :W], 1, 2)
        last = jnp.maximum(learning + forward - 1, 0)[:, None, None]
        held = jnp.sum(jnp.where(j[None, :, None] == last, q, 0), axis=1, keepdims=True)
        return q[:, :L], jnp.where(j[None, F:, None] <= last, q[:, F:], held)

    def by_index(q, a):
        return jnp.take_along_axis(q, a[..., None], axis=-1)[..., 0]

    def td_loss(form, net, online, target, outs_t, target_outs_t, b):
        """`learner.make_loss_fn`'s island on the form's views."""
        pick = by_index if form == "indexed" else _q_at
        burn_in, learning, forward, action, reward, gamma, weight = b
        q_learn, q_boot = views(form, net, online, outs_t, burn_in, learning, forward)
        _, q_boot_target = views(form, net, target, target_outs_t, burn_in, learning, forward)
        mask = (jnp.arange(net.learning_steps)[None, :] < learning[:, None]).astype(jnp.float32)
        a_star = jnp.argmax(jax.lax.stop_gradient(q_boot), axis=-1)
        y = jax.lax.stop_gradient(value_rescale(
            reward + gamma * inverse_value_rescale(pick(q_boot_target, a_star))))
        td = y - pick(q_learn, action)
        loss = jnp.sum(weight[:, None] * jnp.square(td) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss, mixed_td_priorities(jnp.abs(td) * mask, mask, 0.9)

    def programs(form, net):
        @jax.jit
        def updates(online, target, outs_t, target_outs_t, batches):
            def one(carry, x):
                o, target_o, b = x
                (value, prio), grads = jax.value_and_grad(
                    lambda p, o: td_loss(form, net, p, target, o, target_o, b), argnums=(0, 1), has_aux=True,
                )(online, o)
                return (carry[0] + value + jnp.sum(prio), jax.tree.map(jnp.add, carry[1], grads)), None

            zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, (online, outs_t[0])))
            return jax.lax.scan(one, zero, (outs_t, target_outs_t, batches))[0]

        @jax.jit
        def both_views(online, outs_t, burn_in, learning, forward):
            return views(form, net, online, outs_t, burn_in, learning, forward)

        return updates, both_views

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))  # compile + warm
        rounds = []
        for _ in range(5):
            t = time.perf_counter()
            for _ in range(args.reps):
                out = fn(*a)
            jax.block_until_ready(out)
            rounds.append((time.perf_counter() - t) / args.reps)
        return statistics.median(rounds) * 1e3

    rng = np.random.default_rng(0)
    largest = 0.0
    for cell in args.cells:
        B, T, L, F, K = (TINY if args.allow_cpu else CELLS)[cell]
        net = network(L, F)
        online, target = (
            net.init(k, jnp.zeros((1, 1, H), dtype), method="_dueling")
            for k in jax.random.split(jax.random.PRNGKey(0), 2))
        # every update has core outputs of its own, as it has a batch of its own: with one
        # array for all K the compiler lifts whatever does not depend on `burn_in` out of
        # the scan (the heads' forward over all T in `head_all`: PERF.md finding 46.2)
        outs_t, target_outs_t = (jnp.asarray(rng.normal(size=(K, T, B, H)), dtype) for _ in range(2))
        ints = lambda lo, hi, *shape: jnp.asarray(rng.integers(lo, hi + 1, size=(K, B, *shape)), jnp.int32)
        floats = lambda *shape: jnp.asarray(rng.random(size=(K, B, *shape)), jnp.float32)
        # every seam the accumulator can store, short last sequences and cut n-step tails
        batches = (ints(0, T - L - F), ints(1, L), ints(0, F), ints(0, ACTIONS - 1, L),
                   floats(L), 0.9 * floats(L), floats())
        want, read = None, {}
        for form in args.forms:
            updates, both_views = programs(form, net)
            got = jnp.concatenate(both_views(online, outs_t[0], *(x[0] for x in batches[:3])), axis=1)
            want = got if want is None else want
            off = float(jnp.max(jnp.abs(got - want)))
            largest = max(largest, off)
            try:
                compiled = updates.lower(online, target, outs_t, target_outs_t, batches).compile()
            except Exception as e:  # a form the chip's compiler refuses is a reading too
                print(json.dumps({"device": device, "cell": cell, "form": form, "refused": repr(e)[:300]}), flush=True)
                continue
            if args.hlo_dir:
                os.makedirs(args.hlo_dir, exist_ok=True)
                with open(os.path.join(args.hlo_dir, f"{cell}.{form}.txt"), "w") as fh:
                    fh.write(compiled.as_text())
            read[form] = timed(compiled, online, target, outs_t, target_outs_t, batches) / K
            print(json.dumps({
                "device": device, "cell": cell, "form": form, "rows": B, "T": T, "window": L + F, "K": K,
                "update_ms": read[form], "q_max_abs_diff_from_first_form": off,
            }), flush=True)
        if "indexed" in read:
            print(json.dumps({"device": device, "cell": cell, "faster_than_indexed_ms": {
                f: read["indexed"] - ms for f, ms in read.items() if f != "indexed"}}), flush=True)
    return 0 if largest <= 1e-5 else 1


if __name__ == "__main__":
    sys.exit(main())
