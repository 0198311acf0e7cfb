"""The LRU core's recurrence alone, on the chip, at the lru-seq581 cell's shape:

    python runs/lru_recurrence_microbench.py            # (B, T, D, H) = (32, 581, 516, 512), bf16

One JSON line per reading (host clock around `--reps` calls in flight, per
call, median of 5 rounds): the bare kernel forward and reversed with its achieved GB/s (one
read of u and one write of h, re + im: 4 x T*B*H*4 bytes), and the `LRU`
module's forward and loss + gradient by each formulation of the same
recurrence: `scan` (jax.lax.associative_scan), `pallas` (ops/pallas_lru.py)
and `chunk64` (lru_chunk=64, the chunked MXU form ROADMAP S8 asked to be read
once at this shape), each with its largest difference from `scan` as a share
of the output's scale. A microbenchmark, not a cell: its numbers size the
kernel and are recorded in PERF.md as such. Exits 3 without a TPU."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--shape", type=int, nargs=4, default=(32, 581, 516, 512), metavar=("B", "T", "D", "H"))
    p.add_argument("--reps", type=int, default=20)
    p.add_argument("--chunks", type=int, nargs="*", default=[], help="also time the bare kernel at these chunk lengths")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from r2d2_tpu.models.lru import LRU
    from r2d2_tpu.ops import pallas_lru

    if jax.default_backend() != "tpu":
        print("no TPU: a microbenchmark of the chip's kernel has nothing to say here", file=sys.stderr)
        return 3
    B, T, D, H = args.shape
    device = jax.devices()[0].device_kind

    def timed(fn, *a):
        """ms per call with `--reps` calls in flight: one call under a
        blocking host clock reads ~1.1 ms here whatever it runs (the
        round trip), so the device is kept fed and the batch is timed."""
        jax.block_until_ready(fn(*a))  # compile + warm
        rounds = []
        for _ in range(5):
            t = time.perf_counter()
            for _ in range(args.reps):
                out = fn(*a)
            jax.block_until_ready(out)
            rounds.append((time.perf_counter() - t) / args.reps)
        return statistics.median(rounds) * 1e3

    def say(**row):
        print(json.dumps({"device": device, "shape": [B, T, D, H], **row}), flush=True)

    rng = np.random.default_rng(0)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    mod, theta = rng.uniform(0.9, 0.999, H), rng.uniform(0, 6.283, H)
    lam = jnp.asarray(mod * np.cos(theta), jnp.float32), jnp.asarray(mod * np.sin(theta), jnp.float32)
    u, h0 = (f32(T, B, H), f32(T, B, H)), (f32(B, H), f32(B, H))
    moved = 4 * T * B * H * 4
    for chunk in [*args.chunks, pallas_lru.chunk_len(T, B)]:
        for name, call in (("forward", pallas_lru._lru_fwd_call), ("reversed", pallas_lru._lru_rev_call)):
            ms = timed(lambda *a: call(*a, chunk=chunk, interpret=False), *lam, *u, *h0)
            say(what=f"kernel {name}", chunk=chunk, ms=ms, gb_per_s=moved / ms / 1e6,
                roofline_share_pct=100 * moved / 819e9 / (ms / 1e3))
    copy = jax.jit(lambda a, b: (a * 1.5 + b, a - b))  # what one XLA fusion makes of the same bytes
    ms = timed(copy, *u)
    say(what="XLA elementwise, 2 in 2 out, same bytes", ms=ms, gb_per_s=moved / ms / 1e6)

    xs = jnp.asarray(rng.normal(size=(B, T, D)), jnp.bfloat16)
    modules = {
        "scan": LRU(H, in_dim=D, dtype=jnp.bfloat16),
        "pallas": LRU(H, in_dim=D, dtype=jnp.bfloat16, backend="pallas"),
        "chunk64": LRU(H, in_dim=D, dtype=jnp.bfloat16, chunk=64),
    }
    params = modules["scan"].init(jax.random.PRNGKey(0), xs, h0)
    want = None
    for name, m in modules.items():
        fwd = jax.jit(lambda p, x, c, m=m: m.apply(p, x, c))

        def loss(p, x, c, m=m):
            outs, (h_re, h_im) = m.apply(p, x, c)
            return jnp.sum(outs.astype(jnp.float32) ** 2) + jnp.sum(h_re * h_im)

        grad = jax.jit(jax.value_and_grad(loss))
        outs = np.asarray(fwd(params, xs, h0)[0], np.float32)
        g = np.concatenate([np.ravel(x) for x in jax.tree.leaves(grad(params, xs, h0)[1])])
        want = want or (outs, g)
        say(what=f"LRU module, {name}", forward_ms=timed(fwd, params, xs, h0), loss_and_grad_ms=timed(grad, params, xs, h0),
            outs_diff_of_scale=float(np.abs(outs - want[0]).max() / np.abs(want[0]).max()),
            grad_diff_of_scale=float(np.abs(g - want[1]).max() / np.abs(want[1]).max()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
