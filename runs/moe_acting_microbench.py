"""The expert mixtures of each stack cell alone at published widths, as ACTING
runs them: N = `num_actors` = 16 tokens a step through `--layers` mixtures with
weights of their own, forward only, under a `lax.scan` of steps with inputs of
their own, bfloat16 weights made once outside the scan (as the collector's
program has them), on the chip (~2 min). ONE layer's weights (201 / 111 MB)
are loop-invariant and half of them fit the chip's 128 MiB of fast memory, so
with `--layers 1` the compiler keeps what it can there across the scan's
iterations and a form reads UNDER its floor (PERF.md finding 59.1); three
layers compete for that room as a cell's acting step (0.94 GB) does. It ranks the
forms of the held experts' part where every token fits a held expert's rows
(`N <= capacity(N)`): `queue` (`ExpertMixture.queued`: the slot table, the
gather of `held x capacity` rows, the scatter-add; what the program ran before
PR 59), `broadcast` (`ExpertMixture.unqueued`: the held experts' batched
matmuls on x broadcast to `(held, N, D)`, then `sum_e w[n, e] out[e, n]` in
float32), `shared_lhs` (the same with x as ONE left operand, `nd,edf->enf`),
`expert_scan` (a `lax.scan` over the held experts, one expert's matrices a
step) and `committed` (`ExpertMixture.__call__` itself: it must read what the
form it takes reads). Beside each: the weights' bytes at the memory's pace
(the floor of a layer-step) and what the form costs over it. Calls in flight,
never one blocking call. Exits 3 without a TPU; `--allow-cpu` is a tiny smoke
test, not a reading. One JSON line a case.

    python runs/moe_acting_microbench.py [--cells nemotron qwen3-next] [--forms queue broadcast]

Readings: PERF.md finding 59.1 and runs/README.md (chip calls 1-2 of PR 59).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELLS = {"nemotron": "nemotron-twotower-30b-a3b-ep16", "qwen3-next": "qwen3-next-80b-a3b-ep32"}
# the widths `--allow-cpu` puts in place of the published ones, by the family's own key
TINY = {"hidden_size": 64, "n_routed_experts": 16, "num_experts": 16, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 64, "shared_expert_intermediate_size": 32,
        "num_experts_held": 4}
HBM_GB_S = 819.0  # benchmark/peaks.json's v5e


def _ms_a_call(fn, *args, calls: int = 10) -> float:
    import jax

    jax.block_until_ready(fn(*args))  # compile
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    out = [fn(*args) for _ in range(calls)]
    jax.block_until_ready(out)
    return 1000.0 * (time.perf_counter() - t) / calls


def _each(m, weight, chosen):
    """-> w (N, Eh): the weight token n gave held expert e, 0 where it did not choose it, as `unqueued` has it."""
    import jax.numpy as jnp

    mine = (chosen - m.sizes.first_held)[..., None] == jnp.arange(m.sizes.held)
    return jnp.sum(jnp.where(mine, weight[..., None], 0.0), axis=1)


def _expert(m, x, mats):
    """`Experts.__call__` on x (N, D) as ONE left operand and matrices handed
    over, all the held experts' `(Eh, D, F)` or one expert's `(D, F)`."""
    import jax
    import jax.numpy as jnp

    F32, x = jnp.float32, x.astype(m.dtype)
    into = lambda name: jnp.einsum("nd,...df->...nf", x, mats[name].astype(m.dtype), preferred_element_type=F32)
    h = jax.nn.silu(into("gate")) * into("up") if m.sizes.gated else jnp.square(jax.nn.relu(into("up")))
    return jnp.einsum("...nf,...fd->...nd", h.astype(m.dtype), mats["down"].astype(m.dtype), preferred_element_type=F32)


def queue(m, x, weight, chosen):
    return m.queued(x, weight, chosen)[0]


def broadcast(m, x, weight, chosen):
    return m.unqueued(x, weight, chosen)[0]


def shared_lhs(m, x, weight, chosen):
    import jax.numpy as jnp

    out = _expert(m, x, m.experts.variables["params"])                                 # (Eh, N, D)
    return jnp.sum(out * _each(m, weight, chosen).T[..., None], axis=0)


def expert_scan(m, x, weight, chosen):
    import jax
    import jax.numpy as jnp

    step = lambda y, e: (y + e[0][:, None] * _expert(m, x, e[1]), None)
    mats = dict(m.experts.variables["params"])
    return jax.lax.scan(step, jnp.zeros(x.shape, jnp.float32), (_each(m, weight, chosen).T, mats))[0]


FORMS = {"queue": queue, "broadcast": broadcast, "shared_lhs": shared_lhs, "expert_scan": expert_scan}


def _layer_with(form):
    """`ExpertMixture.__call__` with the held experts' part made by `form`."""
    from r2d2_tpu.models import hybrid_stack as hs

    def layer(m, x):
        flat = hs.rms_norm(x, m.pre_norm, m.sizes.eps).reshape(-1, x.shape[-1])
        return x + (form(m, flat, *m.routing(flat)) + m.shared(flat)).reshape(x.shape)
    return layer


def floor_us(s) -> dict:
    """The bytes one layer-step must read, at the memory's pace: the held and
    the shared expert's matrices in bfloat16, the router in float32."""
    mats = 3 if s.gated else 2
    parts = {"held": s.held * mats * s.hidden_size * s.expert_width * 2,
             "shared": mats * s.hidden_size * s.shared_width * 2, "router": s.hidden_size * s.experts * 4}
    return {"weights_mb": {k: v / 1e6 for k, v in parts.items()}, "floor_us": sum(parts.values()) / HBM_GB_S / 1e3}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--allow-cpu", action="store_true")
    p.add_argument("--cells", nargs="+", default=list(CELLS), choices=list(CELLS))
    p.add_argument("--forms", nargs="+", default=[*FORMS, "committed"], choices=[*FORMS, "committed"])
    p.add_argument("--steps", type=int, default=34, help="steps a call (the cells' env steps an update)")
    p.add_argument("--layers", type=int, default=3, help="mixtures a step, one after the other, weights of their own")
    args = p.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from r2d2_tpu.models import hybrid_stack as hs

    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("moe_acting_microbench: no TPU", file=sys.stderr)
        return 3
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    device = {"platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind}
    for cell in args.cells:
        conf = harness.load_json(os.path.join(root, "benchmark", "configs", CELLS[cell] + ".json"))
        if args.allow_cpu:
            core = conf["overrides"]["core_config"]
            core = {**core, **{k: v for k, v in TINY.items() if k in core}}
            conf["overrides"].update(core_config=core, hidden_dim=64)
        cfg = harness.build_config(conf, 0)
        sizes = hs.spec_of(cfg).sizes("E")
        # (the CPU's dot has no bfloat16 x bfloat16 -> float32 for every layout a form asks of it)
        dtype = jnp.dtype("float32" if args.allow_cpu else cfg.resolved_compute_dtype)
        N, D = cfg.num_actors, sizes.hidden_size
        assert N <= sizes.capacity(N), "the cell's acting step does not fit a held expert's rows"
        layer = hs.ExpertMixture(sizes, dtype)
        key = jax.random.PRNGKey(0)
        xs = jax.random.normal(key, (args.steps, N, D))
        # the matrices a matmul casts, cast once: the collector's program hoists the casts out of its scan
        cast = lambda name, v: (jax.tree.map(lambda a: a.astype(dtype), v)
                                if name in ("experts", "shared_up", "shared_down", "shared_gate") else v)
        init = jax.jit(lambda key: {name: cast(name, v) for name, v in layer.init(key, xs[0])["params"].items()})
        params = [init(k) for k in jax.random.split(key, args.layers)]
        floor = floor_us(sizes)
        print(json.dumps({"case": "floor", "cell": cell, "N": N, "capacity": sizes.capacity(N), "held": sizes.held,
                          "steps": args.steps, "layers": args.layers, **floor, **device}), flush=True)
        first = None
        for name in args.forms:
            if name == "committed":
                one = lambda p, x: layer.apply({"params": p}, x)[0]
            else:
                one = lambda p, x, form=_layer_with(FORMS[name]): layer.apply({"params": p}, x, method=form)
            step = lambda params, x, one=one: functools.reduce(lambda x, p: one(p, x), params, x)
            run = jax.jit(lambda params, xs, step=step: jax.lax.scan(lambda _, x: (None, step(params, x)), None, xs)[1])
            ms = _ms_a_call(run, params, xs)
            ys = run(params, xs)
            first = ys if first is None else first
            us = 1e3 * ms / (args.steps * args.layers)
            print(json.dumps({"case": "layer_step", "cell": cell, "form": name, "us_a_layer_step": us,
                              "over_floor_us": us - floor["floor_us"],
                              "max_abs_diff_from_first_form": float(jnp.max(jnp.abs(ys - first))),
                              "max_abs": float(jnp.max(jnp.abs(ys))), **device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
