"""What a learn cell's whole-program limits (`limits` of its configuration
file: `q`, `loss`, `grad_norm`) have to lie between, read through the
harness's own comparison at the cell's size, on the chip, one JSON line a
seed and operating point:

- `sound`: the program against the float32 reference, as `correct` judges it
  (drivers/train_fused.py: the same set-up, the same stored sequences, the
  same `ReferenceCheck`), at the window's start state and at its end state;
- and what has to come out NOT correct, each put in the program's place
  against the same reference numbers:
  `bfloat16`, the reference one precision down (its weights, the batch and the
  stored state rounded to bfloat16, matmuls at the default precision);
  `half_batch`, the program with the second half of the batch left out of the
  loss (`learning_steps` 0 there: a contract fault);
  `state_unchanged`, the program on stored state that was never written (zeros:
  every sequence starts from an empty memory);
  and, for a `hybrid_stack` core, one block of each kind left out of the
  program (its output projection zero in the online and the target
  parameters): `<block>_left_out`.

    python runs/reference_limits.py qwen3-next-80b-a3b-ep32.learn 11 12 13

A limit belongs between `sound`'s largest and the smallest of a fault that it
is meant to tell. Readings: PERF.md finding 56.6."""

from __future__ import annotations

import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _left_out(params, block: str):
    """The flax tree with every output projection of core block `block` zero:
    the residual stream passes it by."""
    import jax

    def zero(path, v):
        names = [getattr(k, "key", None) for k in path]
        return v * 0 if block in names and names[-1] in ("o_proj", "out_proj", "down", "shared_down") else v

    return jax.tree_util.tree_map_with_path(zero, params)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import correct, harness
    from benchmark.drivers import train_fused

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = harness.load_cell(root, argv[0])
    ref = harness.reference_for(cell)
    check = below = None
    for seed in (int(a) for a in argv[1:]):
        ctx = harness.Context(cell=cell, seed=seed, seconds=1.0, trace=False, t_start=time.perf_counter(),
                              require_tpu=jax.devices()[0].platform == "tpu")
        d = train_fused._drive(ctx)
        gc.collect()
        cfg = d["cfg"]
        check = check or correct.ReferenceCheck(ref, cfg, d["net"], cell.config)
        sizes = ref.sizes_of(cfg)
        low = lambda tree: jax.tree.map(
            lambda v: jnp.asarray(v, jnp.bfloat16) if np.asarray(v).dtype == np.float32 else v, tree)
        below = below or jax.jit(lambda p, tp, b: ref.loss_q_gradnorm(p, tp, b, sizes))
        for point in ("start", "end"):
            at = d[point]
            params, target, batch = at["params"], at["target_params"], at["batch"]
            rows = np.arange(batch.learning_steps.shape[0]) < batch.learning_steps.shape[0] // 2
            sides = {
                "sound": lambda: check._program(params, target, batch),
                "half_batch": lambda: check._program(
                    params, target, batch._replace(learning_steps=np.where(rows, batch.learning_steps, 0))),
                "state_unchanged": lambda: check._program(params, target, batch._replace(hidden=np.zeros_like(batch.hidden))),
                "bfloat16": lambda: tuple(v.astype(jnp.float32) for v in below(
                    low(params["params"]), low(target["params"]), low(correct.reference_batch(batch)))),
            }
            blocks = sorted(params["params"].get("core", {})) if cfg.recurrent_core == "hybrid_stack" else []
            for kind in sorted({b.rsplit("_", 1)[0] for b in blocks if b.rsplit("_", 1)[-1].isdigit()}):
                for block in [b for b in blocks if b.rsplit("_", 1)[0] == kind][-1:]:   # the last of each kind
                    sides[block + "_left_out"] = lambda block=block: check._program(
                        _left_out(params, block), _left_out(target, block), batch)
            with jax.default_matmul_precision("highest"):
                want = jax.device_get(check._reference(params["params"], target["params"], correct.reference_batch(batch)))
            out = {"seed": seed, "point": point, "updates": at["updates"], "q_scale": float(np.max(np.abs(want[1])))}
            for name, side in sides.items():
                got = jax.device_get(side())
                v = correct.reference_verdict(got[0], want[0], got[2], want[2], correct.scale_err(got[1], want[1]), check.tol)
                out[name] = {k: v[k] for k in ("q_err_over_scale", "loss_rel", "grad_norm_rel", "ok")}
            print(json.dumps(out), flush=True)
        del d
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
