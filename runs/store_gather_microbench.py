"""The store gather alone, on the chip, at each one-chip cell's real store shape:

    python runs/store_gather_microbench.py     # u8[1280,441,56,128] B=64 T=85, u8[512,1089,56,128] B=32 T=581,
                                               # and nature's store at dp4's 16 rows per chip

One JSON line per form: ms per gather, K gathers to a call under `lax.scan` as
the update scan runs them (each writes the carried batch, so none is dead; the
frames leave as `rows_as_stored` makes them, less its last reshape), `--reps`
calls in flight, median of 5 rounds (one blocking call reads ~1.1 ms whatever
it runs, and one gather to a call ~0.21 ms, the host's pace: PERF.md findings
34.2, 41.1). Forms, each bit-compared with `frames`:

  frames   the PARENT's formula (tests/test_store_gather.parent_gather, the
           plain reference of the tests): one index per frame through
           jnp.take's default mode (a select against the fill value over the
           batch), one (block, row) index pair per entry of the scalar fields
  loop     ISSUE 41's form: every field's window of T - F + 1 (or L) rows
           copied by `dynamic_slice` -> `dynamic_update_slice` in ONE
           `fori_loop` over the B sequences, the clipped tail by the gather
  windows  learner.make_store_gather as committed

with `--parts`, the frames and the scalar fields of each alone. A
microbenchmark, not a cell: its numbers rank forms and are recorded in PERF.md
as such. Exits 3 without a TPU (`--allow-cpu`: a tiny smoke test, no reading)."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SCALARS = ("last_action", "last_reward", "action", "n_step_reward", "gamma")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--cells", nargs="*", default=["nature-lstm512", "lru-seq581", "nature-lstm512:16"],
                   help="a benchmark configuration, optionally :rows for another batch size")
    p.add_argument("--forms", nargs="*", default=["frames", "loop", "windows"])
    p.add_argument("--parts", action="store_true", help="also time the frames and the scalar fields alone")
    p.add_argument("--reps", type=int, default=30)
    p.add_argument("--updates", type=int, default=16, help="K: gathers to a call")
    p.add_argument("--allow-cpu", action="store_true")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from benchmark import harness
    from r2d2_tpu.learner import make_store_gather
    from r2d2_tpu.replay.block import store_field_specs
    from tests.test_store_gather import parent_gather

    if jax.default_backend() != "tpu" and not args.allow_cpu:
        print("no TPU: a microbenchmark of the chip's gather has nothing to say here", file=sys.stderr)
        return 3
    device = jax.devices()[0].device_kind

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

    for cell in args.cells:
        name, _, rows_arg = cell.partition(":")
        cfg = harness.build_config(harness.load_json(os.path.join(ROOT, "benchmark", "configs", name + ".json")), 1)
        if args.allow_cpu:
            cfg = cfg.replace(buffer_capacity=cfg.block_length * 4, batch_size=4)
        if rows_arg:
            cfg = cfg.replace(batch_size=int(rows_arg))
        B, nb, K = cfg.batch_size, cfg.num_blocks, args.updates
        L, T, F = cfg.learning_steps, cfg.seq_len, cfg.forward_steps
        slot, bl, S = cfg.block_slot_len, cfg.block_length, cfg.seqs_per_block
        head = T - F + 1
        specs = store_field_specs(cfg)
        R, n_bytes = specs["obs"][0][1], int(np.prod(cfg.obs_shape))

        # full blocks whose burn-in came over from the block before: win = s L,
        # so the last sequence of every block meets the clip
        rng = np.random.default_rng(0)
        seq = np.arange(S)
        u8 = lambda d: lax.broadcasted_iota(jnp.uint8, (nb, slot, R, 128), d)
        stores = {k: jnp.asarray(rng.normal(size=(nb, *specs[k][0])).astype(specs[k][1])) for k in SCALARS}
        stores |= {
            "obs": jax.jit(lambda: u8(0) * 7 + u8(1) * 13 + u8(2) * 3 + u8(3))(),
            "hidden": jnp.zeros((nb, *specs["hidden"][0]), specs["hidden"][1]),
            "burn_in": jnp.full((nb, S), cfg.burn_in_steps, jnp.int32),
            "learning": jnp.full((nb, S), L, jnp.int32),
            "forward": jnp.asarray(np.broadcast_to(np.minimum(F, bl + 1 - (seq + 1) * L), (nb, S)), jnp.int32),
        }
        bK = jnp.asarray(rng.integers(0, nb, (K, B)), jnp.int32)
        sK = jnp.asarray(rng.integers(0, S, (K, B)), jnp.int32).at[:, 0].set(S - 1)
        per_slot = lambda k: stores[k].shape[1] == slot  # obs, last_*: T rows from `win`; the others L from s L

        def coords(stores, b, s):
            win = stores["burn_in"][b, 0] + s * L - stores["burn_in"][b, s]
            rows = jnp.clip(win[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :], 0, slot - 1)
            return win, rows

        def of(gather):
            def run(stores, b, s):
                batch = gather(stores, b, s, jnp.ones(B, jnp.float32))
                return {k: getattr(batch, k) for k in ("obs", *SCALARS)}
            return run

        def loop(stores, b, s):
            win, rows = coords(stores, b, s)
            fields = ("obs", *SCALARS)
            length = {k: head if per_slot(k) else L for k in fields}
            flats = {k: stores[k].reshape(-1, *stores[k].shape[2:]) for k in fields}
            starts = {k: b * stores[k].shape[1] + (win if per_slot(k) else s * L) for k in fields}
            outs = {k: jnp.zeros((B, T if per_slot(k) else L, *flats[k].shape[1:]), flats[k].dtype) for k in fields}

            def body(i, outs):
                def put(k):
                    w = lax.dynamic_slice_in_dim(flats[k], starts[k][i], length[k], axis=0)
                    return lax.dynamic_update_slice(outs[k], w[None], (i,) + (0,) * w.ndim)
                return {k: put(k) for k in outs}

            outs = lax.fori_loop(0, B, body, outs)
            for k in ("obs", "last_action", "last_reward"):
                tail = jnp.take(flats[k], b[:, None] * slot + rows[:, head:], axis=0, mode="clip")
                outs[k] = lax.dynamic_update_slice(outs[k], tail, (0, head) + (0,) * (tail.ndim - 2))
            return outs

        def part(fn, keys):
            """`keys` of fn's batch, the frames as rows_as_stored makes them less its last reshape."""
            def run(*a):
                out = fn(*a)
                out["obs"] = out["obs"].reshape(B * T, -1)[:, :n_bytes]  # (B, T) merged: PR 43
                return {k: out[k] for k in keys}
            return run

        def scanned(fn):
            def run(stores, bK, sK):
                init = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), jax.eval_shape(fn, stores, bK[0], sK[0]))
                return lax.scan(lambda carry, xs: (fn(stores, *xs), None), init, (bK, sK))[0]
            return jax.jit(run)

        forms = {"frames": of(parent_gather(cfg, as_stored=True)), "loop": loop,
                 "windows": of(make_store_gather(cfg, as_stored=True))}
        bits = lambda out: {k: lax.bitcast_convert_type(v, jnp.int32) if v.dtype == jnp.float32 else v
                            for k, v in out.items()}
        want = bits(jax.jit(part(forms["frames"], ("obs", *SCALARS)))(stores, bK[0], sK[0]))
        parts = {"all": ("obs", *SCALARS)} | ({"obs": ("obs",), "scalars": SCALARS} if args.parts else {})
        for form in args.forms:
            for what, keys in parts.items():
                fn = part(forms[form], keys)
                got = bits(jax.jit(fn)(stores, bK[0], sK[0]))
                print(json.dumps({
                    "device": device, "cell": cell, "store": list(stores["obs"].shape), "B": B, "T": T, "K": K,
                    "form": form, "fields": what, "ms_per_gather": timed(scanned(fn), stores, bK, sK) / K,
                    "bit_equal_to_frames": all(bool(jnp.array_equal(got[k], want[k])) for k in got),
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
