"""`R2D2Network._core_input`'s way back to time order alone, on the chip, as
one update of the two LSTM cells runs it (ISSUE 49's step 0):

    python runs/core_input_microbench.py                  # 64 x 85 and 16 x 85, bf16
    python runs/core_input_microbench.py --cells nature --hlo-dir chiprun_out/core_input_hlo

It STARTS at the encoder's two sub-batches behind the LSTM's seam: each row's
W = L + F encoded frames from its seam `bf16[B, W, 512]` (with gradient) and
its other T - W `bf16[B, T - W, 512]` (without), with `last_action` and
`last_reward` as the batch holds them, `(B, T)` in time order. It ends at a
scalar of the time-ordered `(B, T, 516)` core input (through a matmul, as the
LSTM's input projection consumes it) and that scalar's gradient w.r.t. the
window part. K updates to a call under `lax.scan`, every update with parts,
actions, rewards and `burn_in` of its own, as the step programs run them.
The forms of "back to time order":

  indexed             one-hot and reward gathered beside each part by the
                      part's flat index, the parts concatenated and
                      `take_along_axis` over B x T rows of 516 (the program
                      until PR 49; its transpose is a scatter-add)
  band_square         the same 516-wide concatenation moved by ONE
                      `(B, T, T)` permutation matmul
  select_band         the same 516-wide parts by `r2d2._time_order`: two
                      padded static slices of the no-gradient part under a
                      select on `t < start[b]`, the window by a `(B, T, W)`
                      selection matmul
  select_band_latent  `_time_order` on the 512 latent columns alone; one-hot
                      and reward are formed from the time-ordered `(B, T)`
                      arrays and concatenated behind (no gather of either)

One JSON line per reading: host clock around `--reps` calls in flight, per
UPDATE (a call is K of them), median of 5 rounds (never one blocking call:
PERF.md finding 34.2), with each form's largest difference from `indexed` in
the core input and in the gradient (0 for every form: each moves the same
entries); then one line with each form's distance from `indexed`. A
microbenchmark, not a cell: its numbers rank the forms and are recorded in
PERF.md as such. Exits 3 without a TPU."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# cell -> (B rows per device, T, window W = L + F, K updates per dispatch)
CELLS = {"nature": (64, 85, 45, 16), "dp4": (16, 85, 45, 16)}
TINY = {"nature": (4, 10, 6, 2), "dp4": (1, 10, 6, 2)}
FORMS = ("indexed", "band_square", "select_band", "select_band_latent")
LATENT, TINY_LATENT, ACTIONS, PROJECTED = 512, 32, 3, 128


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

    from r2d2_tpu.models.r2d2 import _time_order

    if jax.default_backend() != "tpu" and not args.allow_cpu:
        print("no TPU: a microbenchmark of the chip's compiler has nothing to say here", file=sys.stderr)
        return 3
    device = jax.devices()[0].device_kind
    dtype = jnp.bfloat16
    D = TINY_LATENT if args.allow_cpu else LATENT

    def beside(latent, action, reward):
        onehot = jax.nn.one_hot(action, ACTIONS, dtype=dtype)
        return jnp.concatenate([latent, onehot, reward.astype(dtype)[..., None]], axis=-1)

    def core_input(form, window, others, action, reward, burn_in):
        """-> (B, T, D + A + 1) in time order, by one form."""
        B, W, _ = window.shape
        T = W + others.shape[1]
        start = jnp.clip(burn_in, 0, T - W).astype(jnp.int32)[:, None]
        if form == "select_band_latent":
            return beside(_time_order(window, others, start[:, 0]), action, reward)
        # the parent's `encode_at`: each part's actions and rewards by its flat index
        window_at = start + jnp.arange(W, dtype=jnp.int32)[None, :]
        c = jnp.arange(T - W, dtype=jnp.int32)[None, :]
        others_at = jnp.where(c < start, c, c + W)
        row0 = jnp.arange(B, dtype=jnp.int32)[:, None] * T

        def part(latent, idx):
            flat = (row0 + idx).reshape(-1)
            take = lambda a: jnp.take(a.reshape(B * T), flat, axis=0, mode="clip").reshape(idx.shape)
            return beside(latent, take(action), take(reward))

        window, others = part(window, window_at), jax.lax.stop_gradient(part(others, others_at))
        if form == "select_band":
            return _time_order(window, others, start[:, 0])
        x = jnp.concatenate([window, others], axis=1)
        t = jnp.arange(T, dtype=jnp.int32)[None, :]
        pos = jnp.where(t < start, W + t, jnp.where(t < start + W, t - start, t))
        if form == "indexed":
            return jnp.take_along_axis(x, pos[:, :, None], axis=1)
        square = pos[:, :, None] == jnp.arange(T, dtype=jnp.int32)[None, None, :]  # band_square
        return jnp.einsum(
            "bts,bsd->btd", square.astype(x.dtype), x,
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32).astype(x.dtype)

    def scalar(form, project, window, others, action, reward, burn_in):
        x = core_input(form, window, others, action, reward, burn_in)
        y = jnp.dot(x, project, preferred_element_type=jnp.float32)
        return jnp.mean(jnp.square(y))

    def programs(form):
        @jax.jit
        def updates(project, parts):
            def one(carry, part):
                value, grad = jax.value_and_grad(lambda w: scalar(form, project, w, *part[1:]))(part[0])
                return (carry[0] + value, carry[1] + grad.astype(jnp.float32)), None

            zero = (jnp.zeros((), jnp.float32), jnp.zeros(parts[0].shape[1:], jnp.float32))
            return jax.lax.scan(one, zero, parts)[0]

        @jax.jit
        def one_update(project, part):
            grad = jax.grad(lambda w: scalar(form, project, w, *part[1:]))(part[0])
            return core_input(form, *part), grad

        return updates, one_update

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))  # warm
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
        B, T, W, K = (TINY if args.allow_cpu else CELLS)[cell]
        project = jnp.asarray(rng.normal(size=(D + ACTIONS + 1, PROJECTED)) / np.sqrt(D), dtype)
        # every update has parts of its own, as it has a batch of its own: what does not
        # depend on `burn_in` would otherwise be lifted out of the scan (PERF.md finding 46.2)
        parts = (
            jnp.asarray(rng.normal(size=(K, B, W, D)), dtype),
            jnp.asarray(rng.normal(size=(K, B, T - W, D)), dtype),
            jnp.asarray(rng.integers(0, ACTIONS, size=(K, B, T)), jnp.int32),
            jnp.asarray(rng.normal(size=(K, B, T)), jnp.float32),
            # every seam the accumulator can store, and one past T - W (the clip)
            jnp.asarray(rng.integers(0, T - W + 2, size=(K, B)), jnp.int32),
        )
        want, read = None, {}
        for form in args.forms:
            updates, one_update = programs(form)
            got = [np.asarray(a, np.float32) for a in one_update(project, tuple(a[0] for a in parts))]
            want = got if want is None else want
            off = [float(np.max(np.abs(g - w))) for g, w in zip(got, want)]
            largest = max(largest, *off)
            try:
                compiled = updates.lower(project, parts).compile()
            except Exception as e:  # a form the chip's compiler refuses is a reading too
                print(json.dumps({"device": device, "cell": cell, "form": form, "refused": repr(e)[:300]}), flush=True)
                continue
            if args.hlo_dir:
                os.makedirs(args.hlo_dir, exist_ok=True)
                with open(os.path.join(args.hlo_dir, f"{cell}.{form}.txt"), "w") as fh:
                    fh.write(compiled.as_text())
            read[form] = timed(compiled, project, parts) / K
            print(json.dumps({
                "device": device, "cell": cell, "form": form, "rows": B, "T": T, "window": W, "K": K,
                "update_ms": read[form], "x_max_abs_diff_from_first_form": off[0],
                "grad_max_abs_diff_from_first_form": off[1],
            }), flush=True)
        if "indexed" in read:
            print(json.dumps({"device": device, "cell": cell, "faster_than_indexed_ms": {
                f: read["indexed"] - ms for f, ms in read.items() if f != "indexed"}}), flush=True)
    return 0 if largest == 0.0 else 1


if __name__ == "__main__":
    sys.exit(main())
