"""The replay -> model hand-over alone, on the chip, as one update of each
benchmark cell runs it (ISSUE 43's step 0):

    python runs/handover_microbench.py                  # the three cells' shapes, bf16
    python runs/handover_microbench.py --cells lru --hlo-dir chiprun_out/handover_hlo

It STARTS where the step programs start: `u8[B, T, 56, 128]` rows gathered out
of a store-shaped `u8[blocks * slot, 56, 128]` array by one flattened, clipped
index (learner.make_store_gather), and ends at loss + gradient of the online
Nature encoder plus the target encoder's forward over the same frames, split
as `R2D2Network._core_input` splits them where the core cuts at burn-in
(nature 64 x 85 with 45 frames a row with gradient, dp4's part per chip 16 x
85, lru 32 x 581 in one call). runs/encoder_conv1_microbench.py starts from
flat `u8[N, 7056]` rows and so never timed what lies between. K updates to a
call under `lax.scan`, each with coordinates of its own, as the programs run
them. Five forms of the hand-over, bit-equal at conv1's input:

  bt           rows.reshape(B, T, 7168)[..., :7056] -> (B, T, 21, 21, 16); the
               model merges (B, T) afterwards (the program until PR 43)
  merged       rows.reshape(B * T, 7168)[:, :7056] -> (B, T, 21, 21, 16): the
               frame index is ONE axis from the gather to the conv
               (replay/block.rows_as_stored since PR 43, called as it is)
  merged_bf16  `merged` with the convert to bf16 BEFORE the flattening: is the
               transposition cheaper at one byte, or fused into the convert at two
  rows         the model is handed (B, T, 56, 128) rows and slices / reshapes
               them at the conv, after its own convert
  unshared     `merged`, and behind the seam each gathered part goes to the
               convert in frame shape behind an `optimization_barrier`: the
               re-layout at one byte, the convert inside the conv fusions, no
               bf16 copy of a part that both nets read (`_core_input` since
               PR 50, which also encodes the two parts one after the other:
               that is about where the CORE's arrays live, PERF.md finding
               50.2, and nothing here holds a core; with no seam, lru, it
               is `merged` to the letter)

One JSON line per reading: host clock around `--reps` calls in flight, per
UPDATE (a call is K of them), median of 5 rounds (never one blocking call:
PERF.md finding 34.2); then one line with each form's distance from `bt`. A
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

# cell -> (B rows per device, T, frames per row with gradient: L + F, or T where
# no seam, K updates per dispatch, frames per block slot, block slots in the store)
CELLS = {
    "nature": (64, 85, 45, 16, 441, 160),
    "lru": (32, 581, 581, 4, 1089, 64),
    "dp4": (16, 85, 45, 16, 441, 160),
}
TINY = {"nature": (2, 6, 4, 2, 9, 3), "lru": (2, 6, 6, 2, 9, 3), "dp4": (1, 6, 4, 2, 9, 3)}
FORMS = ("bt", "merged", "merged_bf16", "rows", "unshared")
# --allow-cpu: the smallest frame the trunk takes whose sides its stride divides
OBS_SHAPE, TINY_OBS_SHAPE, BLOCK = (84, 84, 1), (36, 36, 1), 4


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

    from r2d2_tpu.models.encoders import NatureEncoder, blocked_shape
    from r2d2_tpu.replay.block import LANES, obs_rows, rows_as_stored

    if jax.default_backend() != "tpu" and not args.allow_cpu:
        print("no TPU: a microbenchmark of the chip's compiler has nothing to say here", file=sys.stderr)
        return 3
    device = jax.devices()[0].device_kind
    dtype = jnp.bfloat16
    obs_shape = TINY_OBS_SHAPE if args.allow_cpu else OBS_SHAPE
    n, R = int(np.prod(obs_shape)), obs_rows(obs_shape)
    stored = blocked_shape(obs_shape, BLOCK)
    encoder = NatureEncoder(dtype=dtype, obs_shape=obs_shape)

    def hand_over(form, rows):
        """(B, T, R, 128) uint8 -> what the model is handed as `obs`."""
        B, T = rows.shape[:2]
        if form == "bt":
            return rows.reshape(B, T, R * LANES)[..., :n].reshape(B, T, *stored)
        if form in ("merged", "unshared"):
            return rows_as_stored(rows, obs_shape, BLOCK)  # the program's own, since PR 43
        if form == "merged_bf16":
            return rows.astype(dtype).reshape(-1, R * LANES)[:, :n].reshape(B, T, *stored)
        return rows

    def conv_input(form, frames, behind_seam=False):
        """`_core_input.encode`'s first line: (N, ...) as handed -> conv1's
        (N, 21, 21, 16) input, [0, 1] in bf16."""
        if form == "unshared" and behind_seam:
            frames = jax.lax.optimization_barrier(frames.reshape(-1, *stored))
        x = frames.astype(dtype) / 255.0
        if form == "rows":
            x = x.reshape(-1, R * LANES)[:, :n]
        return x.reshape(-1, *stored)

    def conv_inputs(form, obs, burn_in, W):
        """The model's side, as `R2D2Network.unroll` / `_core_input`: one
        (B * T) batch, or behind the seam each row's W frames from its seam
        (with gradient) and its other T - W (without), by one flattened index."""
        B, T = obs.shape[:2]
        if W == T:
            return [conv_input(form, obs.reshape(B * T, *obs.shape[2:]))]
        start = jnp.clip(burn_in, 0, T - W).astype(jnp.int32)[:, None]
        window = start + jnp.arange(W, dtype=jnp.int32)[None, :]
        c = jnp.arange(T - W, dtype=jnp.int32)[None, :]
        others = jnp.where(c < start, c, c + W)
        row0 = jnp.arange(B, dtype=jnp.int32)[:, None] * T
        frames = obs.reshape(B * T, -1)
        at = lambda idx: conv_input(
            form, jnp.take(frames, (row0 + idx).reshape(-1), axis=0, mode="clip"), behind_seam=True)
        return [at(window), at(others)]

    def programs(form, T, W, slot):
        def gathered(store, b, win):
            t = jnp.arange(T, dtype=jnp.int32)
            at = b[:, None] * slot + jnp.clip(win[:, None] + t[None, :], 0, slot - 1)
            return jnp.take(store, at, axis=0, mode="clip")

        def loss(online, target, store, b, win, burn_in):
            obs = hand_over(form, gathered(store, b, win))
            # each net converts the batch for itself, as `unroll` does twice an update
            latents = lambda params, grad: jnp.concatenate([
                encoder.apply(params, x) if grad and i == 0 else jax.lax.stop_gradient(encoder.apply(params, x))
                for i, x in enumerate(conv_inputs(form, obs, burn_in, W))]).astype(jnp.float32)
            mine, theirs = latents(online, True), latents(target, False)
            return jnp.sum(mine ** 2) + jnp.sum(mine * theirs)

        @jax.jit
        def updates(online, target, store, b, win, burn_in):
            def one(carry, coords):
                value, grads = jax.value_and_grad(loss)(online, target, store, *coords)
                return (carry[0] + value, jax.tree.map(jnp.add, carry[1], grads)), None

            zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, online))
            return jax.lax.scan(one, zero, (b, win, burn_in))[0]

        @jax.jit
        def at_conv(store, b, win, burn_in):
            return jnp.concatenate(conv_inputs(form, hand_over(form, gathered(store, b, win)), burn_in, W))

        return updates, at_conv

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
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    online, target = (encoder.init(k, jnp.zeros((1, *stored), dtype)) for k in keys)
    all_equal = True
    for cell in args.cells:
        B, T, W, K, slot, blocks = (TINY if args.allow_cpu else CELLS)[cell]
        store = jnp.asarray(rng.integers(0, 256, size=(blocks * slot, R, LANES), dtype=np.uint8))
        b = jnp.asarray(rng.integers(0, blocks, size=(K, B)), jnp.int32)
        # a window may run past its slot's end, as a block's last sequence does: the clip
        win = jnp.asarray(rng.integers(0, slot - T + 3, size=(K, B)), jnp.int32)
        burn_in = jnp.asarray(rng.integers(0, max(T - W, 0) + 1, size=(K, B)), jnp.int32)
        want, read = None, {}
        for form in args.forms:
            updates, at_conv = programs(form, T, W, slot)
            got = at_conv(store, b[0], win[0], burn_in[0])
            want = got if want is None else want
            equal = bool(jnp.array_equal(got, want))
            all_equal &= equal
            compiled = updates.lower(online, target, store, b, win, burn_in).compile()
            if args.hlo_dir:
                os.makedirs(args.hlo_dir, exist_ok=True)
                with open(os.path.join(args.hlo_dir, f"{cell}.{form}.txt"), "w") as fh:
                    fh.write(compiled.as_text())
            read[form] = timed(compiled, online, target, store, b, win, burn_in) / K
            print(json.dumps({
                "device": device, "cell": cell, "form": form, "rows": B, "T": T, "K": K,
                "frames_with_gradient": B * W, "update_ms": read[form],
                "bit_equal_at_conv_input": equal,
            }), flush=True)
        if "bt" in read:
            print(json.dumps({"device": device, "cell": cell, "faster_than_bt_ms": {
                f: read["bt"] - ms for f, ms in read.items() if f != "bt"}}), flush=True)
    return 0 if all_equal else 1


if __name__ == "__main__":
    sys.exit(main())
