"""Compile a benchmark configuration's two step programs at REAL size for a
described (not attached) TPU v5e:2x2, here on the CPU, and say what the chip's
compiler made of them. No chip time, nothing runs (on-chip-measurement guide,
section 2, third rehearsal). Run it before a chip call whenever a step
program's structure, a device scope or a kernel name changes:

    JAX_PLATFORMS=cpu python runs/rehearse_step_programs.py nature-lstm512
    JAX_PLATFORMS=cpu python runs/rehearse_step_programs.py nature-lstm512-dp4 --hlo-dir /tmp/hlo

For the collecting program (`mega`) and the update-only one (`multi`) it
prints one JSON line: the module's name (the benchmark's `step_program`
pattern wants jit_mega / jit_multi / jit_body), how many instructions each
category of benchmark/trace_patterns.json matches (`lstm_kernel` the three
Mosaic calls; `store_copy` is a pattern for a 5-D u8 copy, which the 4-D row
store of PR 25 can no longer match, so its 0 proves nothing), every
instruction that re-lays-out something store-sized (`store_sized_relayouts`: a
`copy`, `transpose` or `reshape` whose result is at least half the obs store's bytes,
wherever it sits; must be empty), the compiler's memory count (it refuses what
does not fit 15.75 GB; `temp_gb` is slab and batch only, `alias_gb` the donated
arguments the output reuses: the whole store in `mega`), and how many
instructions carry each device scope of utils/profiling.SPANS and land in each
bucket of benchmark/trace_scopes.json, and how many MB of arrays it writes into
the fast memory (`S(1)` in the layout) under each bucket, by the instruction's
own op_name or its heir's (`s1_mb_by_owner`; with `--against <dir>`, another
tree's `--hlo-dir`, that tree's beside it: whenever the producer or a consumer
of a large array changes, compare before calling the chip). A renamed scope
recompiles the step programs cold on the chip (~200 s, nature): settle names
here.

PR 22 found the whole-store copy and the 20 GB refusal this way; PR 23 settled
its scopes this way (same instruction count as the parent, 9,508 / 4,274); PR
25 chose the store's row format this way (PERF.md finding 25.1)."""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def step_programs(config: str, topo, buffer_capacity=None):
    """-> (cfg, {"mega": (jitted, abstract arguments), "multi": ...}, the obs
    store's bytes per device) for a benchmark configuration at its real size
    on the described topology's devices. The caller steers what the code asks
    the ATTACHED device (`pallas_lstm._interpret`, `vmem_capacity_bytes`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from benchmark import harness
    from r2d2_tpu import learner, megastep
    from r2d2_tpu.collect import default_chunk_len
    from r2d2_tpu.models.r2d2 import R2D2Network
    from r2d2_tpu.replay.block import store_field_specs
    from r2d2_tpu.train import build_fn_env

    conf = harness.load_json(os.path.join(ROOT, "benchmark", "configs", config + ".json"))
    traffic = harness.load_json(os.path.join(ROOT, "benchmark", "traffic", "learn.json"))
    extra = {"samples_per_insert": float(traffic["samples_per_insert"])}
    if buffer_capacity:
        extra["buffer_capacity"] = buffer_capacity
    cfg = harness.build_config(conf, 1, extra)
    if cfg.recurrent_core == "lstm":
        cfg = cfg.replace(lstm_backend="pallas")
    dp = max(cfg.dp_size, 1)
    net = R2D2Network.from_config(cfg)
    if cfg.recurrent_core == "lru" and cfg.lru_chunk == 0:
        # no option names the LRU's kernel: take the module the chip would build
        net = net.clone(core=net.core.clone(backend="pallas"))
    fn_env = build_fn_env(cfg)
    E, K, B, chunk = cfg.num_actors, cfg.updates_per_dispatch, cfg.batch_size, default_chunk_len(cfg)

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    if dp == 1:
        rep = per_dp = coord = SingleDeviceSharding(topo.devices[0])
        kb, key, start = (K, B), sds((2,), jnp.uint32, rep), sds((), jnp.int32, rep)
        mega = megastep.make_megastep(cfg, net, fn_env, E, chunk, K)
        multi = learner.make_fused_multi_train_step(cfg, net, K)
    else:
        mesh = Mesh(np.array(topo.devices[:dp]).reshape(dp, 1), ("dp", "tp"))
        rep, per_dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
        coord = NamedSharding(mesh, P(None, "dp"))
        kb, key, start = (K, dp, B // dp), sds((dp, 2), jnp.uint32, per_dp), sds((dp,), jnp.int32, per_dp)
        mega = megastep.make_sharded_megastep(cfg, net, fn_env, mesh, E, chunk, K)
        multi = learner.make_sharded_fused_multi_train_step(cfg, net, mesh, K)
    state = jax.tree.map(
        lambda x: sds(x.shape, x.dtype, rep),
        jax.eval_shape(lambda: learner.init_train_state(cfg, jax.random.PRNGKey(0))[1]))
    stores = {k: sds((cfg.num_blocks, *shape), dt, per_dp) for k, (shape, dt) in store_field_specs(cfg).items()}
    # per device: under dp the block axis is split over the shards
    obs_store_bytes = int(np.prod(stores["obs"].shape)) // dp
    env = jax.tree.map(
        lambda x: sds(x.shape, x.dtype, per_dp),
        jax.eval_shape(lambda: jax.vmap(fn_env.reset)(jax.random.split(jax.random.PRNGKey(0), E))))
    coords = [sds(kb, jnp.int32, coord), sds(kb, jnp.int32, coord), sds(kb, jnp.float32, coord)]
    programs = {
        "mega": (mega, (state, stores, env, sds((E,), jnp.float32, per_dp), key, *coords, start)),
        "multi": (multi, (state, stores, *coords)),
    }
    return cfg, programs, obs_store_bytes


def _ordered_buckets() -> list:
    """[(bucket, compiled regex)] of benchmark/trace_scopes.json, in the reader's own order."""
    from benchmark.readers import trace_scope

    return [(name, re.compile(rx)) for name, rx in trace_scope.load_scopes(os.path.join(ROOT, "benchmark"))["buckets"]]


def instructions_in_buckets(text: str) -> dict:
    """{bucket of benchmark/trace_scopes.json: named instructions of a
    compiled program's text that it claims}, by the reader's own order."""
    from r2d2_tpu.utils import profiling

    buckets = _ordered_buckets()
    in_bucket = {b: 0 for b, _ in buckets}
    for op in profiling.parse_op_names(text).values():
        hit = next((b for b, rx in buckets if rx.search(op)), None)
        if hit:
            in_bucket[hit] += 1
    return in_bucket


_ARRAY = re.compile(r"([a-z]+\d*)\[([\d,]*)\]\{([^}]*)\}")


def s1_bytes_by_owner(text: str) -> dict:
    """{bucket of benchmark/trace_scopes.json, or "unowned": bytes} of the
    arrays a compiled program writes into the chip's fast memory: every array
    with `S(1)` in its layout in the result of an instruction outside the
    fusions' bodies, under the bucket that wants the instruction's op_name or,
    where it has none, its heir's (profiling.parse_heirs: a prefetch's
    `copy-done` is its consumer's). What hands a value on without writing it
    (`bitcast`, `get-tuple-element`, `tuple`, `parameter`, `while`, a `-start`,
    whose array its `-done` carries) is left out. The count PRs 49, 50 and 55
    made by hand before calling the chip (ROADMAP D9)."""
    from r2d2_tpu.utils import profiling

    buckets = _ordered_buckets()
    owner = {**profiling.parse_op_names(text), **{i: op for i, (op, _) in profiling.parse_heirs(text).items()}}
    out = {**{b: 0 for b, _ in buckets}, "unowned": 0}
    for i in profiling.top_level(profiling.parse_instructions(text)):
        if i.opcode in (*profiling.PLUMBING, "bitcast") or i.opcode.endswith("-start"):
            continue
        size = sum(profiling._DTYPE_BYTES.get(dtype, 0) * math.prod(int(d) for d in dims.split(",") if d)
                   for dtype, dims, layout in _ARRAY.findall(i.shape) if "S(1)" in layout)
        if size:
            op = owner.get(i.name, "")
            out[next((b for b, rx in buckets if rx.search(op)), "unowned")] += size
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("config", help="a name under benchmark/configs/")
    p.add_argument("--hlo-dir", default=None, help="write each program's as_text() here")
    p.add_argument("--buffer-capacity", type=int, default=None,
                   help="compile at another replay capacity than the configuration's (what would fit)")
    p.add_argument("--against", default=None,
                   help="a directory another tree's --hlo-dir wrote: its S(1) bytes by owner are printed beside this tree's")
    args = p.parse_args(argv)

    import jax
    from jax.experimental import topologies

    # a compile for a described device cannot be read back from the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    from benchmark import harness
    from r2d2_tpu.ops import pallas_lstm
    from r2d2_tpu.utils import profiling

    # the code asks the ATTACHED device (a CPU here): steer it to the chip's answers
    pallas_lstm._interpret = lambda: False
    pallas_lstm.vmem_capacity_bytes = lambda: 128 << 20  # v5e

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    _, programs, obs_store_bytes = step_programs(args.config, topo, args.buffer_capacity)
    patterns = harness.load_json(os.path.join(ROOT, "benchmark", "trace_patterns.json"))["categories"]
    device_scopes = [n for n in profiling.SPANS if n.startswith("r2d2_")]
    for name, (fn, fn_args) in programs.items():
        t = time.time()
        compiled = fn.lower(*fn_args).compile()  # raises what the chip's compiler would raise
        text = compiled.as_text()
        if args.hlo_dir:
            os.makedirs(args.hlo_dir, exist_ok=True)
            with open(os.path.join(args.hlo_dir, f"{args.config}.{name}.hlo"), "w") as fh:
                fh.write(text)
        instr = [re.sub(r"^\s*(ROOT )?", "", l) for l in text.splitlines()
                 if re.match(r"\s*(ROOT )?%[\w.\-]+ = ", l)]
        op_names = profiling.parse_op_names(text)
        memory = compiled.memory_analysis()
        row = {
            "program": name, "module": re.search(r"HloModule (\S+?)[,\s]", text).group(1),
            "compile_s": round(time.time() - t, 1), "instructions": len(instr),
            "matches": {k: sum(1 for l in instr if re.search(rx, l)) for k, rx in patterns.items()
                        if k != "step_program"},
            "store_copy": [l.split(", backend_config")[0] for l in instr if re.search(patterns["store_copy"], l)],
            "store_sized_relayouts": profiling.relayouts_at_least(text, obs_store_bytes // 2),
            "lstm_kernels": [l.split(" = ")[0] for l in instr if re.search(patterns["lstm_kernel"], l)],
            "temp_gb": round(memory.temp_size_in_bytes / 1e9, 2),
            "argument_gb": round(memory.argument_size_in_bytes / 1e9, 2),
            "alias_gb": round(memory.alias_size_in_bytes / 1e9, 2),
            "with_op_name": len(op_names),
            "in_scope": {s: sum(f"jit({s})" in v for v in op_names.values()) for s in device_scopes},
            "in_bucket": instructions_in_buckets(text),
            "s1_mb_by_owner": {k: round(v / 1e6, 2) for k, v in s1_bytes_by_owner(text).items()},
        }
        if args.against:
            with open(os.path.join(args.against, f"{args.config}.{name}.hlo")) as fh:
                row["s1_mb_by_owner_against"] = {k: round(v / 1e6, 2) for k, v in s1_bytes_by_owner(fh.read()).items()}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
