"""One Gated DeltaNet layer of `hybrid_stack` alone at the qwen3-next cell's
shape, forward + backward, on the chip (~3 min): the chunk size (64 against
128) and the form of the chunk's unit lower-triangular solve `(I + L)^-1 [w |
u]`, each form put in `delta_rule_chunked`'s solve: `substitution`
(`solve_triangular` over all 256 right-hand columns, `hybrid_stack`'s
fallback), `inverse_then_matmul` (`solve_triangular` for the Q-column inverse,
then one matmul at "highest"), `doubling` (the inverse block by block, `X_2s =
X_s - X_s off_2s X_s` from `X_1 = I`: log2(Q) steps of two matmuls at
"highest", no loop over rows, then the matmul) and `kernel` (PR 57, what
`delta_rule_chunked` runs at this shape: `ops/pallas_delta.py`, the inverse by
rows on the VPU with a triangle a lane, then the matmul; its parts are timed
beside it: the kernel alone on `(Q, Q, N)`, with XLA's two transpositions,
the solve forward). Each form alone on the cell's `(B, n, Hk, R, Q, Q)` array
with its residual on an agent's L (keys that hardly differ: L near `beta`
times all ones, where the series of squarings ISSUE 56 proposed loses every
digit, PERF.md finding 56.3). Calls in flight, never one blocking call. Exits
3 without a TPU; `--allow-cpu` is a tiny smoke test, not a reading. One JSON
line a case.

    python runs/gdn_microbench.py [--forms substitution kernel] [--chunks 64]

Readings: PERF.md finding 56.3 (the three XLA forms) and 57.1 (the kernel:
chip call 1 of PR 57, `--forms substitution kernel --chunks 64`, PRNGKey(0)).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ms_a_call(fn, *args, calls: int = 10) -> float:
    import jax

    jax.block_until_ready(fn(*args))  # compile
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    out = [fn(*args) for _ in range(calls)]
    jax.block_until_ready(out)
    return 1000.0 * (time.perf_counter() - t) / calls


def _highest(a, b):
    import jax
    import jax.numpy as jnp

    return jnp.einsum("...ij,...jk->...ik", a, b, precision=jax.lax.Precision.HIGHEST)


def substitution(L, rhs):
    import jax
    import jax.numpy as jnp

    return jax.scipy.linalg.solve_triangular(jnp.eye(L.shape[-1]) + L, rhs, lower=True, unit_diagonal=True)


def inverse_then_matmul(L, rhs):
    import jax.numpy as jnp

    return _highest(substitution(L, jnp.broadcast_to(jnp.eye(L.shape[-1]), L.shape)), rhs)


def doubling(L, rhs):
    """The inverse of the s x s diagonal blocks gives that of the 2s x 2s
    ones: `[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]`, on whole
    (Q, Q) matrices under masks, so no axis shorter than Q appears."""
    import jax.numpy as jnp

    Q = L.shape[-1]
    i = jnp.arange(Q)
    X, s = jnp.broadcast_to(jnp.eye(Q), L.shape), 1
    while s < Q:
        block = lambda size: (i[:, None] // size) == (i[None, :] // size)
        X = X - _highest(_highest(X, jnp.where(block(2 * s) & ~block(s), L, 0.0)), X)
        s *= 2
    return _highest(X, rhs)


def kernel(L, rhs):
    """ops/pallas_delta.py: the inverse by rows in a kernel, a triangle a lane,
    then the matmul; its backward pass is two matmuls on the saved inverse."""
    from r2d2_tpu.ops import pallas_delta

    return pallas_delta.unit_lower_solve(L, rhs)


FORMS = {"substitution": substitution, "inverse_then_matmul": inverse_then_matmul, "doubling": doubling,
         "kernel": kernel}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--allow-cpu", action="store_true")
    p.add_argument("--forms", nargs="+", default=list(FORMS), choices=list(FORMS))
    p.add_argument("--chunks", nargs="+", type=int, default=[64, 128])
    args = p.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from r2d2_tpu.models import hybrid_stack as hs
    from r2d2_tpu.ops import pallas_delta, pallas_lstm

    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("gdn_microbench: no TPU", file=sys.stderr)
        return 3
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf = harness.load_json(os.path.join(root, "benchmark", "configs", "qwen3-next-80b-a3b-ep32.json"))
    if args.allow_cpu:
        core = dict(conf["overrides"]["core_config"], hidden_size=64, linear_num_key_heads=4, linear_key_head_dim=16,
                    linear_num_value_heads=8, linear_value_head_dim=16, num_attention_heads=4, head_dim=16,
                    num_experts=16, num_experts_per_tok=2, moe_intermediate_size=32,
                    shared_expert_intermediate_size=32, num_experts_held=4)
        conf["overrides"].update(core_config=core, hidden_dim=64, batch_size=8, learning_steps=64, burn_in_steps=8)
    cfg = harness.build_config(conf, 0)
    sizes = hs.spec_of(cfg).sizes("D")
    B, T, dtype = cfg.batch_size, cfg.seq_len, jnp.dtype(cfg.resolved_compute_dtype)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, T, sizes.hidden_size))
    delta = jnp.zeros((B, sizes.value_heads, sizes.key_dim, sizes.value_dim))
    tail = jnp.zeros((B, sizes.conv_kernel - 1, sizes.conv_dim))
    device = {"platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind, "B": B, "T": T}
    fits = pallas_delta.kernel_fits
    for chunk in args.chunks:
        layer = hs.GatedDeltaNet(dataclasses.replace(sizes, chunk=chunk), dtype)
        params = jax.jit(layer.init)(key, x, delta, tail)
        loss = lambda p, x: jnp.sum(jnp.square(layer.apply(p, x, delta, tail)[0]))
        # the solve alone, as the layer has it: L (B, n, Hk, R, Q, Q), 2 x 128 right-hand columns
        n = -(-T // chunk)
        shape = (B, n, sizes.key_heads, sizes.value_heads // sizes.key_heads, chunk)
        L = jnp.tril(0.95 + 0.01 * jax.random.normal(key, shape + (chunk,)), -1)   # an agent's: keys that hardly differ
        rhs = jax.random.normal(key, shape + (sizes.key_dim + sizes.value_dim,))
        for name in args.forms:
            form = FORMS[name]
            # the fallback's place; the kernel is what the layer takes by itself where its shape test passes
            hs.unit_lower_solve, pallas_delta.kernel_fits = form, (fits if name == "kernel" else lambda *_: False)
            ms = _ms_a_call(jax.jit(jax.grad(loss, argnums=(0, 1))), params, x)
            print(json.dumps({"case": "layer_fwd_bwd", "form": name, "chunk": chunk, "ms": ms, **device}), flush=True)
            ms = _ms_a_call(jax.jit(jax.grad(lambda L, rhs: jnp.sum(jnp.square(form(L, rhs))), argnums=(0, 1))), L, rhs)
            residual = float(jnp.max(jnp.abs(_highest(jnp.eye(chunk) + L, form(L, rhs)) - rhs)))
            print(json.dumps({"case": "solve_fwd_bwd", "form": name, "chunk": chunk, "ms": ms,
                              "residual": residual, **device}), flush=True)
            if name == "kernel":   # its parts: the kernel alone on (Q, Q, N), with its two transpositions, the solve forward
                lt = jnp.moveaxis(L.reshape(-1, chunk, chunk), 0, 2)
                for part, fn, operands in (
                    ("kernel_alone", lambda lt: pallas_delta._gdn_inverse_call(lt, interpret=pallas_lstm._interpret()), (lt,)),
                    ("inverse", pallas_delta.unit_lower_inverse, (L,)),
                    ("solve_fwd", pallas_delta.unit_lower_solve, (L, rhs)),
                ):
                    print(json.dumps({"case": part, "form": name, "chunk": chunk,
                                      "ms": _ms_a_call(jax.jit(fn), *operands), **device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
