"""The Nature encoder alone, on the chip, as one update of each benchmark cell runs it:

    python runs/encoder_conv1_microbench.py                 # the three cells' shapes, bf16
    python runs/encoder_conv1_microbench.py --cells nature  # one of them

What is timed is loss + gradient of the online encoder over a batch of
`u8[B*T, 7056]` frame rows plus the target encoder's forward over the same
rows with other parameters, split as `R2D2Network._core_input` splits it
where the core cuts at burn-in (nature: 64 x 85 rows = 2,880 frames with
gradient + 2,560 without, twice; dp4's part per chip: 16 x 85 = 720 + 640;
lru: 32 x 581 = 18,592 in one call, twice). Three forms of conv1's input,
the same maths:

  canon    rows in canonical order -> (N, 84, 84, 1) -> the 8x8/4 conv over
           one channel (the program until PR 38; kept HERE as the plain form)
  ingraph  rows in canonical order -> 4x4 block transposition in the graph ->
           (N, 21, 21, 16) -> the 2x2/1 conv (models/encoders.BlockedConv fed
           canonical frames: acting, the host planes)
  blocked  rows already in block order -> (N, 21, 21, 16) by a reshape ->
           the same 2x2/1 conv (the step programs over a device store)
  dot      `blocked`, conv1 as one dot_general over its four shifted views,
           (N*400, 64) x (64, 32) (ISSUE 38's fallback, read once)

One JSON line per reading: host clock around `--reps` calls in flight, per
call, median of 5 rounds (never one blocking call: PERF.md finding 34.2), and
the form's largest latent difference from `canon` as a share of the latents'
scale. A microbenchmark, not a cell: its numbers rank the forms and are
recorded in PERF.md as such. Exits 3 without a TPU."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# cell -> (B rows per device, T, frames per row with gradient: L + F, or T where no seam)
CELLS = {"nature": (64, 85, 45), "lru": (32, 581, 581), "dp4": (16, 85, 45)}
OBS_SHAPE, BLOCK = (84, 84, 1), 4


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--cells", nargs="*", default=list(CELLS), choices=list(CELLS))
    p.add_argument("--forms", nargs="*", default=["canon", "ingraph", "blocked", "dot"])
    p.add_argument("--reps", type=int, default=30)
    p.add_argument("--allow-cpu", action="store_true", help="run tiny on the CPU (a smoke test, no reading)")
    args = p.parse_args(argv)

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from r2d2_tpu.models.encoders import NatureEncoder, block_frames, blocked_shape

    if jax.default_backend() != "tpu" and not args.allow_cpu:
        print("no TPU: a microbenchmark of the chip's compiler has nothing to say here", file=sys.stderr)
        return 3
    device = jax.devices()[0].device_kind
    dtype = jnp.bfloat16

    class CanonEncoder(nn.Module):
        """The encoder with conv1 as `nn.Conv(32, (8, 8), 4)`: same tree."""

        @nn.compact
        def __call__(self, x):
            x = x.astype(dtype)
            x = nn.relu(nn.Conv(32, (8, 8), strides=(4, 4), padding="VALID", dtype=dtype)(x))
            x = nn.relu(nn.Conv(64, (4, 4), strides=(2, 2), padding="VALID", dtype=dtype)(x))
            x = nn.relu(nn.Conv(64, (3, 3), strides=(1, 1), padding="VALID", dtype=dtype)(x))
            return nn.relu(nn.Dense(512, dtype=dtype)(x.reshape((x.shape[0], -1))))

    class DotEncoder(nn.Module):
        """`blocked` with conv1 as one matmul over its four shifted views."""

        @nn.compact
        def __call__(self, x):
            kernel = self.param("k", nn.initializers.lecun_normal(), (8, 8, 1, 32))
            bias = self.param("b", nn.initializers.zeros_init(), (32,))
            k2 = kernel.reshape(2, 4, 2, 4, 1, 32).transpose(0, 2, 1, 3, 4, 5).reshape(64, 32)
            x = x.astype(dtype)
            views = [x[:, a:a + 20, b:b + 20, :] for a in (0, 1) for b in (0, 1)]
            x = jnp.concatenate(views, axis=-1).reshape(-1, 64) @ k2.astype(dtype)
            x = nn.relu(x.reshape(-1, 20, 20, 32) + bias.astype(dtype))
            x = nn.relu(nn.Conv(64, (4, 4), strides=(2, 2), padding="VALID", dtype=dtype)(x))
            x = nn.relu(nn.Conv(64, (3, 3), strides=(1, 1), padding="VALID", dtype=dtype)(x))
            return nn.relu(nn.Dense(512, dtype=dtype)(x.reshape((x.shape[0], -1))))

    nature = NatureEncoder(dtype=dtype, obs_shape=OBS_SHAPE)
    as_stored = blocked_shape(OBS_SHAPE, BLOCK)
    # form -> (module, the order its rows are in, the shape a row is reshaped to)
    forms = {
        "canon": (CanonEncoder(), "canonical", OBS_SHAPE),
        "ingraph": (nature, "canonical", OBS_SHAPE),
        "blocked": (nature, "blocked", as_stored),
        "dot": (DotEncoder(), "blocked", as_stored),
    }

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

    def update_of(module, frame_shape, B, T, W):
        """(online, target, rows, burn_in) -> (loss, gradient of online)."""

        def encode(params, rows):
            x = rows.reshape(-1, *frame_shape).astype(dtype) / 255.0
            return module.apply(params, x).astype(jnp.float32)

        def latents(params, rows, burn_in):
            if W == T:
                return encode(params, rows)
            # R2D2Network._core_input: each row's W frames from its seam with
            # gradient, its other T - W without, by one flattened row index
            start = jnp.clip(burn_in, 0, T - W).astype(jnp.int32)[:, None]
            window = start + jnp.arange(W, dtype=jnp.int32)[None, :]
            c = jnp.arange(T - W, dtype=jnp.int32)[None, :]
            others = jnp.where(c < start, c, c + W)
            row0 = jnp.arange(B, dtype=jnp.int32)[:, None] * T
            at = lambda idx: encode(params, jnp.take(rows, (row0 + idx).reshape(-1), axis=0, mode="clip"))
            return jnp.concatenate([at(window), jax.lax.stop_gradient(at(others))], axis=0)

        def loss(online, target, rows, burn_in):
            mine = latents(online, rows, burn_in)
            theirs = jax.lax.stop_gradient(latents(target, rows, burn_in))
            return jnp.sum(mine ** 2) + jnp.sum(mine * theirs)

        return jax.jit(jax.value_and_grad(loss)), jax.jit(latents)

    rng = np.random.default_rng(0)
    for cell in args.cells:
        B, T, W = CELLS[cell] if not args.allow_cpu else (2, 6, 4 if CELLS[cell][2] < CELLS[cell][1] else 6)
        frames = rng.integers(0, 256, size=(B * T, *OBS_SHAPE), dtype=np.uint8)
        rows = {
            "canonical": jnp.asarray(frames.reshape(B * T, -1)),
            "blocked": jnp.asarray(block_frames(frames, OBS_SHAPE, BLOCK).reshape(B * T, -1)),
        }
        burn_in = jnp.asarray(rng.integers(0, max(T - W, 0) + 1, size=B), jnp.int32)
        keys = jax.random.split(jax.random.PRNGKey(0), 2)
        want = None
        for form in args.forms:
            module, order, frame_shape = forms[form]
            init = lambda k: module.init(k, jnp.zeros((1, *frame_shape), dtype))
            if form == "dot":
                # the canonical tree, with conv1's pair under the names DotEncoder gives them
                def init(k, like=init):
                    canon = forms["canon"][0].init(k, jnp.zeros((1, *OBS_SHAPE), dtype))["params"]
                    mine = dict(like(k)["params"])
                    conv1 = canon["Conv_0"]
                    return {"params": {**mine, "k": conv1["kernel"], "b": conv1["bias"],
                                       "Conv_0": canon["Conv_1"], "Conv_1": canon["Conv_2"], "Dense_0": canon["Dense_0"]}}
            online, target = init(keys[0]), init(keys[1])
            update, latents = update_of(module, frame_shape, B, T, W)
            got = np.asarray(latents(online, rows[order], burn_in))
            want = got if want is None else want
            print(json.dumps({
                "device": device, "cell": cell, "form": form, "rows": B * T,
                "frames_with_gradient": B * W, "frames_without": 2 * B * T - B * W,
                "update_ms": timed(update, online, target, rows[order], burn_in),
                "forward_ms": timed(latents, target, rows[order], burn_in),
                "latents_diff_of_scale": float(np.abs(got - want).max() / np.abs(want).max()),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
