"""Jaxpr scanners over the canonical compiled entry points.

The AST rules catch what the *source* says; these catch what the *traced
program* actually does. Each scanner traces one canonical entry point —
the stacked-batch train step (the tiered plane's consumer), the batched
act step (the actor fleet's policy call), and the serve step — at a given
precision and asserts the dtype/donation contracts the precision policy
promises:

- no float64 anywhere, either precision (x64 is off; an f64 op on TPU
  would double memory and fall off the MXU);
- the fp32 golden path is bf16-free (bit-exactness contract);
- the bf16 path keeps its fp32 islands (loss/target/priority math) AND
  actually computes in bf16 (otherwise the precision knob is dead);
- donated TrainState buffers are fully consumed: every donated leaf's
  (shape, dtype) reappears in the outputs, so XLA can alias in place
  (the silent-copy failure mode);
- host-padded block fields agree exactly with `store_field_specs` — the
  donated device-store `_write` requires vals dtypes to match the store
  buffers (the PR-4 `pad_block_fields` bug class: a float32 `hidden` slab
  against a bf16 store).

Traces are tiny (config.tiny_test shapes) and cached with lru_cache keyed
by precision, so the tier-1 gate and the per-precision tests share one
trace per entry point per precision across the whole pytest process.

Findings use path "<jaxpr:LABEL>" with line 0 — there is no source line
for a traced program; the label names the entry point and precision.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

from r2d2_tpu.analysis.findings import Finding

# jax and the model stack import lazily inside the cached helpers so that
# `python -m r2d2_tpu.analysis` (AST lints only) stays cheap.


def _finding(rule: str, label: str, message: str, hint: str = "",
             severity: str = "error") -> Finding:
    return Finding(
        rule=rule, severity=severity, path=f"<jaxpr:{label}>",
        line=0, col=0, message=message, hint=hint,
    )


@functools.lru_cache(maxsize=None)
def _cfg(precision: str):
    from r2d2_tpu.config import tiny_test

    return tiny_test().replace(precision=precision)


@functools.lru_cache(maxsize=None)
def _net_and_state(precision: str):
    import jax

    from r2d2_tpu.learner import init_train_state

    cfg = _cfg(precision)
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    return net, state


def _state_struct(cfg, *lead):
    """Aval of a stored recurrent state with leading axes `lead`: the
    core's own shape at cfg.state_dtype (models/core.py)."""
    import jax

    from r2d2_tpu.models.core import state_spec

    shape, dtype = state_spec(cfg)
    return jax.ShapeDtypeStruct((*lead, *shape), dtype)


def _carry_struct(cfg, num_envs: int):
    """Avals of the acting carry for `num_envs` rows."""
    import jax

    from r2d2_tpu.models.core import zero_carry

    return jax.eval_shape(lambda: zero_carry(cfg, num_envs))


def _stacked_batch_struct(precision: str, num_steps: int):
    """ShapeDtypeStructs of a (K, B, ...) stacked DeviceBatch at tiny_test
    shapes — tracing needs only avals, not data."""
    return _stacked_struct_from_cfg(_cfg(precision), num_steps)


def _stacked_struct_from_cfg(cfg, num_steps: int):
    import jax

    from r2d2_tpu.learner import DeviceBatch

    K, B, T, L = num_steps, cfg.batch_size, cfg.seq_len, cfg.learning_steps
    sds = jax.ShapeDtypeStruct
    return DeviceBatch(
        obs=sds((K, B, T, *cfg.obs_shape), np.uint8),
        last_action=sds((K, B, T), np.int32),
        last_reward=sds((K, B, T), np.float32),
        hidden=_state_struct(cfg, K, B),
        action=sds((K, B, L), np.int32),
        n_step_reward=sds((K, B, L), np.float32),
        gamma=sds((K, B, L), np.float32),
        burn_in_steps=sds((K, B), np.int32),
        learning_steps=sds((K, B), np.int32),
        forward_steps=sds((K, B), np.int32),
        is_weights=sds((K, B), np.float32),
    )


_NUM_STEPS = 2  # K of the stacked train step: >1 so the scan is real


@functools.lru_cache(maxsize=None)
def train_step_jaxpr(precision: str) -> str:
    """Jaxpr text of the stacked-batch train step (the canonical learner
    entry point: every other step builder shares its _raw_train_step
    body)."""
    import jax

    from r2d2_tpu.learner import make_stacked_batch_train_step

    cfg = _cfg(precision)
    net, state = _net_and_state(precision)
    step = make_stacked_batch_train_step(cfg, net, _NUM_STEPS, donate=False)
    return str(jax.make_jaxpr(step)(state, _stacked_batch_struct(precision, _NUM_STEPS)))


def _multitask_cfg(precision: str):
    """The multi-task trace config: 2 tasks over a union action space, so
    the task leaf exists in the batch and the head carries the one-hot
    task conditioning + per-task action masking."""
    return _cfg(precision).replace(
        num_tasks=2,
        action_dim=5,
        multitask_envs=("drift", "banditgrid"),
        task_action_dims=(3, 5),
        task_gammas=(0.997, 0.99),
    )


@functools.lru_cache(maxsize=None)
def multitask_train_step_jaxpr(precision: str) -> str:
    """Jaxpr text of the TASK-CONDITIONED stacked train step (num_tasks >
    1): the multi-task plane's learner entry point — same _raw_train_step
    body as the golden path plus the (K, B) task leaf driving the one-hot
    head widening and the per-task valid-action mask."""
    import jax

    from r2d2_tpu.learner import init_train_state, make_stacked_batch_train_step

    cfg = _multitask_cfg(precision)
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    step = make_stacked_batch_train_step(cfg, net, _NUM_STEPS, donate=False)
    batch = _stacked_batch_struct(precision, _NUM_STEPS)._replace(
        task=jax.ShapeDtypeStruct((_NUM_STEPS, cfg.batch_size), np.int32)
    )
    return str(jax.make_jaxpr(step)(state, batch))


@functools.lru_cache(maxsize=None)
def resharded_train_step_jaxpr(precision: str, dp: int = 2) -> str:
    """Jaxpr text of the sharded fused train step traced on a RESHARD-
    target mesh shape (dp=2). Elastic resume (replay/reshard.py) compiles
    the train step on whatever layout the scheduler hands back, not just
    the dp the run started with — so the gate traces that layout too."""
    import jax

    from r2d2_tpu.learner import make_sharded_fused_multi_train_step
    from r2d2_tpu.parallel.mesh import make_mesh
    from r2d2_tpu.replay.block import store_field_specs

    cfg = _cfg(precision).replace(replay_plane="sharded", dp_size=dp)
    net, state = _net_and_state(precision)
    mesh = make_mesh(dp=dp, tp=1, devices=jax.devices()[:dp])
    step = make_sharded_fused_multi_train_step(cfg, net, mesh, 1, donate=False)
    sds = jax.ShapeDtypeStruct
    stores = {
        k: sds((cfg.num_blocks, *shape), dt)
        for k, (shape, dt) in store_field_specs(cfg).items()
    }
    B = cfg.batch_size // dp
    coords = (
        sds((1, dp, B), np.int32),  # per-shard LOCAL block ids
        sds((1, dp, B), np.int32),  # sequence-in-block
        sds((1, dp, B), np.float32),  # IS weights
    )
    return str(jax.make_jaxpr(step)(state, stores, *coords))


@functools.lru_cache(maxsize=None)
def act_jaxpr(precision: str, num_envs: int = 4) -> str:
    """Jaxpr text of the batched act step (VectorizedActor._policy's
    body: one net.act over the env fleet)."""
    import jax

    cfg = _cfg(precision)
    net, state = _net_and_state(precision)
    sds = jax.ShapeDtypeStruct
    E = num_envs

    def policy(params, obs, la, lr, carry):
        return net.apply(params, obs, la, lr, carry, method=net.act)

    return str(
        jax.make_jaxpr(policy)(
            state.params,
            sds((E, *cfg.obs_shape), np.uint8),
            sds((E,), np.int32),
            sds((E,), np.float32),
            _carry_struct(cfg, E),
        )
    )


@functools.lru_cache(maxsize=None)
def _pallas_net_and_state(precision: str):
    """Net + state with the Pallas backend forced (the TPU learner path).

    CPU tracing is fine: make_jaxpr only abstracts the pallas_call (the
    init's one interpret-mode forward at tiny shapes is cheap)."""
    import jax

    from r2d2_tpu.learner import init_train_state

    cfg = _cfg(precision).replace(lstm_backend="pallas")
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    return net, state


@functools.lru_cache(maxsize=None)
def fused_unroll_jaxpr(precision: str):
    """ClosedJaxpr of the forward sequence unroll on the Pallas backend —
    the fused-sequence kernel's canonical entry (ops/pallas_lstm.py
    lstm_seq_unroll via models/lstm.py). The Pallas entry points return
    the jaxpr OBJECT (str() it for the text checkers): the launch counter
    walks equations, which printed text cannot support — jax prints a
    jitted sub-function called twice ONCE and refers to it by name."""
    import jax

    cfg = _cfg(precision)
    net, state = _pallas_net_and_state(precision)
    B, T = cfg.batch_size, cfg.seq_len
    sds = jax.ShapeDtypeStruct

    def unroll(params, obs, la, lr, hid, bi, ls, fs):
        return net.apply(params, obs, la, lr, hid, bi, ls, fs)

    return jax.make_jaxpr(unroll)(
        state.params,
        sds((B, T, *cfg.obs_shape), np.uint8),
        sds((B, T), np.int32),
        sds((B, T), np.float32),
        _state_struct(cfg, B),
        sds((B,), np.int32),
        sds((B,), np.int32),
        sds((B,), np.int32),
    )


@functools.lru_cache(maxsize=None)
def fused_train_step_jaxpr(precision: str):
    """ClosedJaxpr of the stacked train step on the Pallas backend: the
    program the TPU learner actually runs, traced so the kernel-launch
    budget (2 forward + 1 backward sequence kernels per update) is gated
    statically."""
    import jax

    from r2d2_tpu.learner import make_stacked_batch_train_step

    cfg = _cfg(precision).replace(lstm_backend="pallas")
    net, state = _pallas_net_and_state(precision)
    step = make_stacked_batch_train_step(cfg, net, _NUM_STEPS, donate=False)
    return jax.make_jaxpr(step)(state, _stacked_batch_struct(precision, _NUM_STEPS))


_SUPERSTEP_N = 2  # dispatches: >1 so the outer scan over dispatch keys is real


@functools.lru_cache(maxsize=None)
def _superstep_cfg(precision: str):
    """tiny_test on the device priority plane — the config family the
    superstep is built for (replay store + sum tree both HBM-resident)."""
    return _cfg(precision).replace(
        replay_plane="device",
        priority_plane="device",
        superstep_dispatches=_SUPERSTEP_N,
        updates_per_dispatch=_NUM_STEPS,
        # step target plays no role in the trace; any N*K multiple is valid
        training_steps=_SUPERSTEP_N * _NUM_STEPS,
    )


def _superstep_inputs(precision: str):
    """(stores, tree, num_seq_store, key) avals for the superstep trace —
    shapes pinned to the DeviceReplayBuffer layout (store_field_specs) and
    the flat f32 sum tree (device_sum_tree.tree_size)."""
    import jax

    from r2d2_tpu.replay import device_sum_tree as dst
    from r2d2_tpu.replay.block import store_field_specs

    cfg = _superstep_cfg(precision)
    sds = jax.ShapeDtypeStruct
    stores = {
        k: sds((cfg.num_blocks, *shape), dt)
        for k, (shape, dt) in store_field_specs(cfg).items()
    }
    L = dst.tree_layers(cfg.num_sequences)
    tree = sds((dst.tree_size(L),), np.float32)
    nss = sds((cfg.num_blocks,), np.int32)
    return stores, tree, nss, jax.random.PRNGKey(0)


@functools.lru_cache(maxsize=None)
def priority_superstep_jaxpr(precision: str) -> str:
    """Jaxpr text of the N×K priority superstep (megastep.
    make_priority_superstep): in-jit stratified sum-tree descent, IS
    weights, K fused train updates, and priority write-back chained over
    N dispatches — the whole program the host re-enters around when
    priority_plane='device'."""
    import jax

    from r2d2_tpu.megastep import make_priority_superstep

    cfg = _superstep_cfg(precision)
    net, state = _net_and_state(precision)
    ss = make_priority_superstep(cfg, net, _SUPERSTEP_N, _NUM_STEPS, donate=False)
    stores, tree, nss, key = _superstep_inputs(precision)
    return str(jax.make_jaxpr(ss)(state, stores, tree, nss, key))


@functools.lru_cache(maxsize=None)
def act_select_jaxpr(precision: str, num_envs: int = 4) -> str:
    """Jaxpr text of the fused act tail (net.act_select: core step +
    dueling combine + ε-greedy select as one program — the body shared by
    actor.py, collect.py, and the serve step)."""
    import jax

    cfg = _cfg(precision)
    net, state = _net_and_state(precision)
    sds = jax.ShapeDtypeStruct
    E = num_envs

    def policy(params, obs, la, lr, carry, explore, rand_a):
        return net.apply(
            params, obs, la, lr, carry, explore, rand_a, method=net.act_select
        )

    return str(
        jax.make_jaxpr(policy)(
            state.params,
            sds((E, *cfg.obs_shape), np.uint8),
            sds((E,), np.int32),
            sds((E,), np.float32),
            _carry_struct(cfg, E),
            sds((E,), bool),
            sds((E,), np.int32),
        )
    )


@functools.lru_cache(maxsize=None)
def _multi_serve_server(precision: str, quantization: str = "none",
                        dp: int = 2):
    from r2d2_tpu.serve.multi import MultiDeviceServer
    from r2d2_tpu.serve.server import ServeConfig

    cfg = _cfg(precision).replace(
        serve_quantization=quantization, serve_devices=dp, serve_spill=4,
    )
    # smallest legal multi-serve plane: one bucket per replica, spill tier
    # on (so the traced step is the one the spilling server runs); never
    # started
    return MultiDeviceServer(cfg, ServeConfig(buckets=(2,), cache_capacity=2))


@functools.lru_cache(maxsize=None)
def multi_serve_step_jaxpr(precision: str, quantization: str = "none",
                           dp: int = 2, replica: int = 0) -> str:
    """Jaxpr text of one replica's serve step in the multi-device server
    (serve/multi.py) at the smallest bucket. Call once per replica: the
    texts must agree (tracing is placement-independent; a difference means
    a replica's step closed over device-dependent state)."""
    import jax

    cfg = _cfg(precision)
    server = _multi_serve_server(precision, quantization, dp)
    rep = server.replicas[replica]
    bucket = rep.batcher.buckets[0]
    h, c, la, lr = rep.cache.arrays()
    sds = jax.ShapeDtypeStruct
    return str(
        jax.make_jaxpr(rep._step)(
            rep._published[0], h, c, la, lr,
            sds((bucket, *cfg.obs_shape), np.uint8),
            sds((bucket,), np.float32),
            sds((bucket,), np.int32),
            sds((bucket,), bool),
            sds((bucket,), bool),
            sds((bucket,), np.int32),
        )
    )


@functools.lru_cache(maxsize=None)
def _serve_server(precision: str, quantization: str = "none"):
    from r2d2_tpu.serve.server import PolicyServer, ServeConfig

    cfg = _cfg(precision).replace(serve_quantization=quantization)
    # smallest legal serve plane: one bucket, cache == bucket; never started
    return PolicyServer(cfg, ServeConfig(buckets=(2,), cache_capacity=2))


@functools.lru_cache(maxsize=None)
def serve_step_jaxpr(precision: str, quantization: str = "none") -> str:
    """Jaxpr text of the serve step (PolicyServer._build_step's jitted
    body) at the smallest bucket."""
    import jax

    cfg = _cfg(precision)
    server = _serve_server(precision, quantization)
    bucket = server.batcher.buckets[0]
    h, c, la, lr = server.cache.arrays()
    sds = jax.ShapeDtypeStruct
    return str(
        jax.make_jaxpr(server._step)(
            server._published[0], h, c, la, lr,
            sds((bucket, *cfg.obs_shape), np.uint8),
            sds((bucket,), np.float32),
            sds((bucket,), np.int32),
            sds((bucket,), bool),
            sds((bucket,), bool),
            sds((bucket,), np.int32),
        )
    )


# ----------------------------------------------------------- dtype checkers


def check_no_float64(jaxpr_text: str, label: str) -> List[Finding]:
    """No f64 arrays anywhere in the traced program, either precision."""
    if "f64[" in jaxpr_text:
        return [
            _finding(
                "jaxpr-float64", label,
                "traced program materializes float64 arrays: x64 must stay "
                "off (f64 doubles memory and falls off the MXU)",
                hint="find the widening op (np.float64 scalar reaching a "
                "jnp op is the usual source) and pin float32",
            )
        ]
    return []


def check_no_bf16(jaxpr_text: str, label: str) -> List[Finding]:
    """The fp32 golden path must be bf16-free (bit-exactness contract)."""
    if "bf16[" in jaxpr_text:
        return [
            _finding(
                "jaxpr-bf16-in-fp32", label,
                "bf16 arrays inside the fp32 golden path: the bit-exact "
                "contract (precision='fp32') is broken",
                hint="a cast to cfg.resolved_compute_dtype is leaking; the "
                "golden path must stay float32 end to end",
            )
        ]
    return []


def check_no_host_callback(jaxpr_text: str, label: str) -> List[Finding]:
    """No host callbacks inside a hot compiled step: a pure_callback /
    io_callback / debug_callback primitive means every execution round-
    trips to Python on the host — a per-batch sync that serializes the
    device against the GIL (the serve step must stay device-only between
    the batch's H2D lift and the result's D2H readback)."""
    hits = [
        name for name in ("pure_callback", "io_callback", "debug_callback")
        if name in jaxpr_text
    ]
    if hits:
        return [
            _finding(
                "jaxpr-host-callback", label,
                f"traced program contains host callback primitive(s) "
                f"{hits}: every execution blocks on a Python round trip",
                hint="move the host-side work outside the jitted step "
                "(batch formation / commit), or precompute it as an input",
            )
        ]
    return []


def check_fp32_island(jaxpr_text: str, label: str) -> List[Finding]:
    """Under bf16 the program must BOTH compute in bf16 (else the precision
    knob is dead) AND keep f32 ops (the loss/target/priority islands)."""
    out: List[Finding] = []
    if "bf16[" not in jaxpr_text:
        out.append(
            _finding(
                "jaxpr-no-bf16-under-bf16", label,
                "precision='bf16' traced a program with no bf16 arrays: the "
                "compute plane silently stayed float32",
                hint="check resolved_compute_dtype reaches the model cores",
            )
        )
    if "f32[" not in jaxpr_text:
        out.append(
            _finding(
                "jaxpr-missing-fp32-island", label,
                "no float32 ops under bf16: the fp32 correctness islands "
                "(Q-target/value-rescale/TD/loss math) have been narrowed",
                hint="learner.loss_fn must cast target/TD math to float32 "
                "regardless of compute dtype",
            )
        )
    return out


# ---------------------------------------------------- kernel-launch checker


def count_pallas_launches(jaxpr) -> int:
    """pallas_call equations in a traced program, counted per CALL SITE:
    every sub-jaxpr (pjit bodies, scan/cond/while bodies, custom-vjp
    calls) is walked each time an equation refers to it, so a jitted
    kernel wrapper invoked twice with equal shapes counts two — the
    printed text shows such a function once. Loop bodies count once (the
    static launch sites of the program, not trip counts)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    n = 0
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1  # the kernel body cannot launch kernels: no descent
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, (ClosedJaxpr, Jaxpr)):
                    n += count_pallas_launches(sub)
    return n


def check_kernel_launch_count(jaxpr, label: str, expected: int,
                              what: str) -> List[Finding]:
    """The fused-sequence contract: the whole T-step unroll is ONE
    pallas_call (and a train step is exactly 2 forward + 1 backward
    launches). A count above `expected` means the sequence got split back
    into per-step or per-segment launches; 0 means the Pallas backend
    silently fell off the traced path. Takes the ClosedJaxpr, not its
    text (count_pallas_launches)."""
    n = count_pallas_launches(jaxpr)
    if n != expected:
        return [
            _finding(
                "jaxpr-kernel-launch-count", label,
                f"{what}: expected exactly {expected} pallas_call "
                f"launch(es) in the traced program, found {n}",
                hint="the sequence kernel must stay fused — one launch per "
                "unroll (ops/pallas_lstm.py), never per timestep/segment",
            )
        ]
    return []


def check_int8_weights(jaxpr_text: str, label: str) -> List[Finding]:
    """The int8 serve arm must actually carry int8 weight arrays into the
    step (else the quantization knob is dead) and must dequantize to the
    compute dtype, never widening to f64."""
    out: List[Finding] = []
    if "i8[" not in jaxpr_text:
        out.append(
            _finding(
                "jaxpr-no-int8-under-int8", label,
                "serve_quantization='int8' traced a step with no int8 "
                "arrays: the quantized publish path is not reaching the "
                "jitted step",
                hint="PolicyServer.prepare_for_publish must run at every "
                "publish point (init and reload_now)",
            )
        )
    return out


# -------------------------------------------------------- donation checkers


def _leaf_specs(tree) -> List[Tuple[Tuple[int, ...], str]]:
    import jax

    return sorted(
        (tuple(l.shape), str(l.dtype)) for l in jax.tree.leaves(tree)
    )


def compare_donated_leaves(donated_tree, out_tree, label: str) -> List[Finding]:
    """Core of the donation rule, reusable on any (donated input, output)
    pytree pair: every donated leaf's (shape, dtype) must reappear in the
    outputs (multiset match) or XLA silently copies instead of aliasing."""
    missing = []
    out_specs = _leaf_specs(out_tree)
    for spec in _leaf_specs(donated_tree):
        if spec in out_specs:
            out_specs.remove(spec)
        else:
            missing.append(spec)
    if missing:
        return [
            _finding(
                "jaxpr-donation-mismatch", label,
                f"donated leaves with no matching output buffer "
                f"(shape, dtype): {missing[:4]}{'...' if len(missing) > 4 else ''} "
                "— XLA cannot alias them and falls back to a copy",
                hint="keep the output leaf shapes/dtypes identical to the "
                "donated input's",
            )
        ]
    return []


def check_train_state_donation(precision: str) -> List[Finding]:
    """Donated TrainState must be FULLY consumed: the output state's leaf
    (shape, dtype) multiset must equal the input's, leaf for leaf, or XLA
    silently copies instead of aliasing (and on real HBM the 'donated'
    buffer is wasted)."""
    import jax

    from r2d2_tpu.learner import make_stacked_batch_train_step

    label = f"train_step[{precision}].donation"
    cfg = _cfg(precision)
    net, state = _net_and_state(precision)
    step = make_stacked_batch_train_step(cfg, net, _NUM_STEPS, donate=True)
    out_state, _, _ = jax.eval_shape(
        step, state, _stacked_batch_struct(precision, _NUM_STEPS)
    )
    return compare_donated_leaves(state, out_state, label)


def compare_store_fields(vals: Dict[str, np.ndarray], specs, label: str) -> List[Finding]:
    """Core of the store-dtype rule, reusable on any (padded vals, field
    specs) pair: the donated device-store writes require an exact
    shape+dtype match per field."""
    out: List[Finding] = []
    for k, (shape, dtype) in specs.items():
        if k not in vals:
            out.append(
                _finding(
                    "jaxpr-store-field-mismatch", label,
                    f"store field {k!r} has a spec but pad_block_fields "
                    "does not produce it",
                    hint="extend pad_block_fields alongside store_field_specs",
                )
            )
            continue
        got = vals[k]
        if got.dtype != np.dtype(dtype) or got.shape != tuple(shape):
            out.append(
                _finding(
                    "jaxpr-store-field-mismatch", label,
                    f"store field {k!r}: padded block gives "
                    f"{got.dtype}{list(got.shape)}, store expects "
                    f"{np.dtype(dtype)}{list(shape)} — the donated _write "
                    "jit needs an exact match",
                    hint="pad with the spec's dtype/shape from "
                    "store_field_specs (single source of truth)",
                )
            )
    for k in vals:
        if k not in specs:
            out.append(
                _finding(
                    "jaxpr-store-field-mismatch", label,
                    f"pad_block_fields produces {k!r} with no store spec",
                    hint="extend store_field_specs alongside pad_block_fields",
                )
            )
    return out


def check_store_field_dtypes(precision: str) -> List[Finding]:
    """pad_block_fields output must agree with store_field_specs exactly —
    the device store's donated `_write` jit requires vals dtypes == store
    dtypes (the PR-4 bug class: an f32 hidden slab against a bf16 store
    retraces or fails the donation)."""
    from r2d2_tpu.models.core import zero_state
    from r2d2_tpu.replay.block import Block, store_field_specs
    from r2d2_tpu.replay.device_store import DeviceReplayBuffer

    label = f"store_write[{precision}].dtypes"
    cfg = _cfg(precision)
    S, n, bl = cfg.seqs_per_block, cfg.block_slot_len - 1, cfg.block_length
    # accumulator-packed dtypes: uint8 actions, float32 hidden (the store
    # downcasts at write time)
    block = Block(
        obs=np.zeros((n, *cfg.obs_shape), np.uint8),
        last_action=np.zeros(n, np.uint8),
        last_reward=np.zeros(n, np.float32),
        action=np.zeros(bl, np.uint8),
        n_step_reward=np.zeros(bl, np.float32),
        gamma=np.zeros(bl, np.float32),
        hidden=zero_state(cfg, S),
        num_sequences=S,
        burn_in_steps=np.full(S, cfg.burn_in_steps, np.int32),
        learning_steps=np.full(S, cfg.learning_steps, np.int32),
        forward_steps=np.full(S, cfg.forward_steps, np.int32),
    )
    vals = DeviceReplayBuffer.pad_block_fields(cfg, block)
    return compare_store_fields(vals, store_field_specs(cfg), label)


def check_trace_budget(trace_count: int, buckets: Sequence[int],
                       label: str = "serve_step",
                       arms: int = 1) -> List[Finding]:
    """The serve step may trace at most once per batch bucket PER weight
    arm (`arms` > 1 when a degrade ladder pre-warms its quality arms'
    executables at warmup); more means an unstable cache key (a recompile
    per request shape) slipped in."""
    if trace_count > arms * len(buckets):
        return [
            _finding(
                "jaxpr-trace-budget", label,
                f"serve step traced {trace_count} times for "
                f"{len(buckets)} bucket shape(s) x {arms} arm(s): some "
                "input's shape/dtype "
                "or a static arg is varying per call",
                hint="pad requests to the bucket shapes; keep every other "
                "input's aval fixed",
            )
        ]
    return []


# ----------------------------------------------------------- entry points


def scan_train_step(precision: str) -> List[Finding]:
    label = f"train_step[{precision}]"
    text = train_step_jaxpr(precision)
    out = check_no_float64(text, label)
    if precision == "fp32":
        out += check_no_bf16(text, label)
    else:
        out += check_fp32_island(text, label)
    out += _check_train_outputs(precision)
    return out


def _check_train_outputs(precision: str) -> List[Finding]:
    """Metrics/priorities leave the step float32 at either precision (the
    host-side consumers — priority tree, jsonl metrics — assume it)."""
    import jax

    from r2d2_tpu.learner import make_stacked_batch_train_step

    label = f"train_step[{precision}].outputs"
    cfg = _cfg(precision)
    net, state = _net_and_state(precision)
    step = make_stacked_batch_train_step(cfg, net, _NUM_STEPS, donate=False)
    _, metrics, prios = jax.eval_shape(
        step, state, _stacked_batch_struct(precision, _NUM_STEPS)
    )
    out: List[Finding] = []
    if str(prios.dtype) != "float32":
        out.append(
            _finding(
                "jaxpr-output-dtype", label,
                f"priorities leave the train step as {prios.dtype}, host "
                "priority tree expects float32",
                hint="mixed_td_priorities runs in the fp32 island; keep it",
            )
        )
    for k, v in metrics.items():
        if str(v.dtype) != "float32":
            out.append(
                _finding(
                    "jaxpr-output-dtype", label,
                    f"metric {k!r} leaves the train step as {v.dtype}, "
                    "expected float32",
                    hint="metrics are loss-island values; keep them f32",
                )
            )
    return out


def scan_multitask_train_step(precision: str) -> List[Finding]:
    """The task-conditioned train step (num_tasks > 1) under the same
    dtype contracts as the golden path: no f64, fp32 path bf16-free, bf16
    path keeps its fp32 islands, no host callbacks. The task one-hot and
    the valid-action mask must not smuggle in a wider dtype."""
    label = f"multitask_train_step[{precision}]"
    text = multitask_train_step_jaxpr(precision)
    out = check_no_float64(text, label)
    out += check_no_host_callback(text, label)
    if precision == "fp32":
        out += check_no_bf16(text, label)
    else:
        out += check_fp32_island(text, label)
    return out


def scan_resharded_train_step(precision: str, dp: int = 2) -> List[Finding]:
    """The train step on a resharded mesh shape: a regression visible only
    under the post-resume partitioning (a float64 creeping into the
    re-split path, a bf16 leak under the dp=2 layout) fails statically
    instead of at the first elastic resume on hardware. No-op when the
    platform has fewer than dp devices."""
    import jax

    if len(jax.devices()) < dp:
        return []
    label = f"resharded_train_step[dp={dp},{precision}]"
    text = resharded_train_step_jaxpr(precision, dp)
    out = check_no_float64(text, label)
    if precision == "fp32":
        out += check_no_bf16(text, label)
    else:
        out += check_fp32_island(text, label)
    return out


def scan_act(precision: str) -> List[Finding]:
    label = f"act[{precision}]"
    text = act_jaxpr(precision)
    out = check_no_float64(text, label)
    if precision == "fp32":
        out += check_no_bf16(text, label)
    else:
        # act has no loss island: only the no-silent-fp32 half applies
        out += [
            f for f in check_fp32_island(text, label)
            if f.rule == "jaxpr-no-bf16-under-bf16"
        ]
    return out


def scan_fused_unroll(precision: str) -> List[Finding]:
    """The fused-sequence kernel entry: dtype contracts plus the one-
    launch-per-unroll budget (and 3 per train step — 2 forwards for
    online/target nets, 1 backward walking the seam-masked reverse
    grid)."""
    label = f"fused_unroll[{precision}]"
    jaxpr = fused_unroll_jaxpr(precision)
    out = check_no_float64(str(jaxpr), label)
    out += check_kernel_launch_count(
        jaxpr, label, 1, "forward sequence unroll"
    )
    ts_label = f"fused_train_step[{precision}]"
    ts_jaxpr = fused_train_step_jaxpr(precision)
    ts_text = str(ts_jaxpr)
    out += check_no_float64(ts_text, ts_label)
    if precision == "fp32":
        out += check_no_bf16(ts_text, ts_label)
    else:
        out += check_fp32_island(ts_text, ts_label)
    out += check_kernel_launch_count(
        ts_jaxpr, ts_label, 3,
        "train step (online fwd + target fwd + backward sequence kernels)",
    )
    return out


def scan_superstep(precision: str) -> List[Finding]:
    """The N×K priority superstep entry: the tree descent / IS-weight /
    write-back math must stay off f64 at either precision (the device
    tree IS the f32 arm of the host-parity contract — an f64 op would
    mean the drift bound is being met by accident), the fp32 golden path
    stays bf16-free, the bf16 path keeps its loss/target/priority
    islands, and the donated (state, tree) pair is fully consumed so XLA
    aliases both in place across the N-dispatch scan."""
    import jax

    from r2d2_tpu.megastep import make_priority_superstep

    label = f"priority_superstep[N{_SUPERSTEP_N}xK{_NUM_STEPS},{precision}]"
    text = priority_superstep_jaxpr(precision)
    out = check_no_float64(text, label)
    if precision == "fp32":
        out += check_no_bf16(text, label)
    else:
        out += check_fp32_island(text, label)
    # donation contract of the production build (donate_argnums=(0, 2)):
    # every TrainState leaf and the tree buffer must reappear unchanged in
    # (shape, dtype) or the superstep silently copies 2x the model + tree
    cfg = _superstep_cfg(precision)
    net, state = _net_and_state(precision)
    ss = make_priority_superstep(cfg, net, _SUPERSTEP_N, _NUM_STEPS, donate=True)
    stores, tree, nss, key = _superstep_inputs(precision)
    out_state, out_tree, _ = jax.eval_shape(ss, state, stores, tree, nss, key)
    out += compare_donated_leaves(state, out_state, f"{label}.donation")
    if (tuple(out_tree.shape), str(out_tree.dtype)) != (
        tuple(tree.shape), str(tree.dtype)
    ):
        out.append(
            _finding(
                "jaxpr-donation-mismatch", f"{label}.donation",
                f"superstep returns a tree of {out_tree.dtype}"
                f"{list(out_tree.shape)} against a donated "
                f"{tree.dtype}{list(tree.shape)} input — the HBM tree "
                "cannot alias in place across dispatches",
                hint="tree_update must preserve the flat f32 layout "
                "(replay/device_sum_tree.py)",
            )
        )
    return out


def scan_act_select(precision: str) -> List[Finding]:
    """The fused act tail (dueling + ε-mask + argmax with the core
    step)."""
    import jax

    label = f"act_select[{precision}]"
    text = act_select_jaxpr(precision)
    out = check_no_float64(text, label)
    if precision == "fp32":
        out += check_no_bf16(text, label)
    else:
        out += [
            f for f in check_fp32_island(text, label)
            if f.rule == "jaxpr-no-bf16-under-bf16"
        ]
    # the selected actions must leave as int32 (host/device parity: every
    # caller stores them into int32 slabs)
    cfg = _cfg(precision)
    net, state = _net_and_state(precision)
    sds = jax.ShapeDtypeStruct
    E = 4
    _, action, _ = jax.eval_shape(
        lambda p, o, la, lr, cy, ex, ra: net.apply(
            p, o, la, lr, cy, ex, ra, method=net.act_select
        ),
        state.params,
        sds((E, *cfg.obs_shape), np.uint8),
        sds((E,), np.int32),
        sds((E,), np.float32),
        _carry_struct(cfg, E),
        sds((E,), bool),
        sds((E,), np.int32),
    )
    if str(action.dtype) != "int32":
        out.append(
            _finding(
                "jaxpr-output-dtype", label,
                f"fused act tail emits {action.dtype} actions, expected "
                "int32 (ops/act_tail.py contract)",
            )
        )
    return out


def scan_serve_step_int8(precision: str = "fp32") -> List[Finding]:
    """The int8 serve arm: int8 weights actually present, dequant lands on
    the compute dtype (no f64 widening, fp32 arm stays bf16-free)."""
    label = f"serve_step[int8,{precision}]"
    text = serve_step_jaxpr(precision, "int8")
    out = check_no_float64(text, label)
    out += check_int8_weights(text, label)
    if precision == "fp32":
        out += check_no_bf16(text, label)
    return out


def scan_multi_serve_step(precision: str, quantization: str = "none",
                          dp: int = 2) -> List[Finding]:
    """The multi-device serve step (serve/multi.py): every replica's
    jitted step must keep the single-device contracts — no f64, no host
    sync (callback primitives) inside the per-device step, int8 weights
    present under the quantized arm — AND all replicas must trace to the
    IDENTICAL program, which is what makes per-session results replica-
    independent (bit-parity with the single-device act path is then a
    placement property, pinned dynamically by tests/test_serve.py).
    No-op when the platform has fewer than dp devices."""
    import jax

    if len(jax.local_devices()) < dp:
        return []
    out: List[Finding] = []
    texts = []
    for i in range(dp):
        label = f"multi_serve_step[d{i}/{dp},{quantization},{precision}]"
        text = multi_serve_step_jaxpr(precision, quantization, dp, i)
        texts.append(text)
        out += check_no_float64(text, label)
        out += check_no_host_callback(text, label)
        if quantization == "int8":
            out += check_int8_weights(text, label)
        if precision == "fp32":
            out += check_no_bf16(text, label)
    # object reprs inside the text (custom_jvp thunks) carry memory
    # addresses that differ per trace; strip them before comparing
    import re

    normalized = {re.sub(r"0x[0-9a-f]+", "0x", t) for t in texts}
    if len(normalized) > 1:
        out.append(
            _finding(
                "jaxpr-replica-divergence",
                f"multi_serve_step[{quantization},{precision}]",
                f"the {dp} serve replicas traced to different programs: "
                "a replica's step closed over device- or index-dependent "
                "state, so per-session results depend on placement",
                hint="the step must be a pure function of (params, stores, "
                "batch inputs); placement belongs to the buffers, not the "
                "program",
            )
        )
    return out


def scan_serve_step(precision: str) -> List[Finding]:
    import jax

    label = f"serve_step[{precision}]"
    text = serve_step_jaxpr(precision)
    out = check_no_float64(text, label)
    out += check_no_host_callback(text, label)
    if precision == "fp32":
        out += check_no_bf16(text, label)
    # q must come back f32 for the host-side argpartition/audit path
    cfg = _cfg(precision)
    server = _serve_server(precision)
    bucket = server.batcher.buckets[0]
    h, c, la, lr = server.cache.arrays()
    sds = jax.ShapeDtypeStruct
    q, action, h2, c2, *_ = jax.eval_shape(
        server._step,
        server._published[0], h, c, la, lr,
        sds((bucket, *cfg.obs_shape), np.uint8),
        sds((bucket,), np.float32),
        sds((bucket,), np.int32),
        sds((bucket,), bool),
        sds((bucket,), bool),
        sds((bucket,), np.int32),
    )
    if str(q.dtype) != "float32":
        out.append(
            _finding(
                "jaxpr-output-dtype", label,
                f"served q values leave the step as {q.dtype}, expected "
                "float32 (dueling head math is an fp32 island)",
            )
        )
    if (h2.dtype, h2.shape) != (h.dtype, h.shape) or (c2.dtype, c2.shape) != (
        c.dtype, c.shape
    ):
        out.append(
            _finding(
                "jaxpr-donation-mismatch", label,
                "serve step returns carry stores whose shape/dtype differ "
                "from the donated input stores — in-place aliasing breaks "
                "and the cache dtype contract drifts",
                hint="cast h_new/c_new to the store dtype before the "
                "scatter (server._build_step does this explicitly)",
            )
        )
    return out


def _liveloop_gather_shapes(precision: str):
    import jax
    import jax.numpy as jnp

    cfg = _cfg(precision)
    H = cfg.hidden_dim
    dt = jnp.bfloat16 if "bfloat16" in str(cfg.state_dtype) else jnp.float32
    sds = jax.ShapeDtypeStruct
    # capacity+1 rows (scratch slot included), a 2-row batch gather
    return (sds((5, H), dt), sds((5, H), dt), sds((2,), jnp.int32))


def liveloop_gather_jaxpr(precision: str) -> str:
    import jax

    from r2d2_tpu.liveloop.tap import gather_carry_rows

    return str(jax.make_jaxpr(gather_carry_rows)(*_liveloop_gather_shapes(precision)))


def scan_liveloop_gather(precision: str) -> List[Finding]:
    """The live-loop tap's only device program: the per-batch carry-row
    gather off the committed session stores (liveloop/tap.py). It runs on
    the serve loop, so it inherits the serve step's hygiene bar — no f64
    upcasts, no host callbacks — and must hand the accumulators float32
    carries regardless of the cache dtype (the stored-state contract)."""
    import jax

    from r2d2_tpu.liveloop.tap import gather_carry_rows

    label = f"liveloop_gather[{precision}]"
    text = liveloop_gather_jaxpr(precision)
    out = check_no_float64(text, label)
    out += check_no_host_callback(text, label)
    h_rows, c_rows = jax.eval_shape(
        gather_carry_rows, *_liveloop_gather_shapes(precision)
    )
    for name, leaf in (("h", h_rows), ("c", c_rows)):
        if str(leaf.dtype) != "float32":
            out.append(
                _finding(
                    "jaxpr-output-dtype", label,
                    f"tap {name}-carry rows leave the gather as "
                    f"{leaf.dtype}, expected float32 (SequenceAccumulator "
                    "stores (2, H) f32 hidden state)",
                    hint="gather_carry_rows must .astype(float32) after "
                    "the take — the cache may hold bf16",
                )
            )
    return out


def scan_donation(precision: str) -> List[Finding]:
    return check_train_state_donation(precision) + check_store_field_dtypes(precision)


# ------------------------------------------------- manual tp x fsdp entries


@functools.lru_cache(maxsize=None)
def _manual_cfg(precision: str, dp: int, tp: int, fsdp: int):
    """tiny_test pinned to the tp x fsdp cell — the mesh shape PR 14's
    validate() used to block, now served by the explicit shard_map path.
    lstm_backend="scan" because tp shards the cell kernels
    (models/r2d2.from_config resolves pallas off under tp_shards_params)."""
    return _cfg(precision).replace(
        lstm_backend="scan", dp_size=dp, tp_size=tp, fsdp_size=fsdp
    )


def _manual_batch_struct(precision: str, dp: int, tp: int, fsdp: int):
    """Single (unstacked) DeviceBatch avals at tiny_test shapes — the
    manual step consumes one host-plane batch per call (train._HostPlane
    lifts exactly this layout onto the (dp, fsdp) data axes)."""
    import jax

    from r2d2_tpu.learner import DeviceBatch

    cfg = _manual_cfg(precision, dp, tp, fsdp)
    B, T, L = cfg.batch_size, cfg.seq_len, cfg.learning_steps
    sds = jax.ShapeDtypeStruct
    return DeviceBatch(
        obs=sds((B, T, *cfg.obs_shape), np.uint8),
        last_action=sds((B, T), np.int32),
        last_reward=sds((B, T), np.float32),
        hidden=_state_struct(cfg, B),
        action=sds((B, L), np.int32),
        n_step_reward=sds((B, L), np.float32),
        gamma=sds((B, L), np.float32),
        burn_in_steps=sds((B,), np.int32),
        learning_steps=sds((B,), np.int32),
        forward_steps=sds((B,), np.int32),
        is_weights=sds((B,), np.float32),
    )


@functools.lru_cache(maxsize=None)
def manual_train_step_jaxpr(precision: str, dp: int, tp: int, fsdp: int) -> str:
    """Jaxpr text of the explicitly-partitioned (shard_map) train step on
    the dp x tp x fsdp mesh: per-shard AD under the 1/tp loss scaling, the
    tp gate-seam all_gathers, the dp(+tp) psum / fsdp psum_scatter
    gradient reduction, sharded Adam, and the fsdp all_gather back to
    replicated params — all explicit collectives in the trace instead of
    GSPMD-inferred ones (the inference that miscompiled this cell)."""
    import jax

    from r2d2_tpu.learner import init_train_state, make_manual_train_step
    from r2d2_tpu.parallel.mesh import make_mesh

    cfg = _manual_cfg(precision, dp, tp, fsdp)
    _net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(dp=dp, tp=tp, fsdp=fsdp)
    step = make_manual_train_step(cfg, mesh, donate=False)
    return str(
        jax.make_jaxpr(step)(state, _manual_batch_struct(precision, dp, tp, fsdp))
    )


def check_manual_train_step_donation(
    precision: str, dp: int, tp: int, fsdp: int
) -> List[Finding]:
    """Donation contract of the manual path's production build
    (donate_argnums=(0,)): every TrainState leaf must reappear in (shape,
    dtype) or the collectives force a second resident copy of the model +
    moments per device."""
    import jax

    from r2d2_tpu.learner import init_train_state, make_manual_train_step
    from r2d2_tpu.parallel.mesh import make_mesh

    label = f"manual_train_step[dp={dp},tp={tp},fsdp={fsdp},{precision}].donation"
    cfg = _manual_cfg(precision, dp, tp, fsdp)
    _net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(dp=dp, tp=tp, fsdp=fsdp)
    step = make_manual_train_step(cfg, mesh, donate=True)
    out_state, _, _ = jax.eval_shape(
        step, state, _manual_batch_struct(precision, dp, tp, fsdp)
    )
    return compare_donated_leaves(state, out_state, label)


def scan_manual_train_step(
    precision: str, dp: int = 2, tp: int = 2, fsdp: int = 2
) -> List[Finding]:
    """The tp x fsdp train step (learner.make_manual_train_step): the
    shard_mapped program holds the same dtype contracts as the golden path
    (no f64; fp32 plane bf16-free; bf16 plane keeps its fp32 loss/target/
    priority islands), no host callbacks, and still donates the whole
    TrainState. No-op when the platform has fewer than dp*tp*fsdp
    devices."""
    import jax

    if len(jax.devices()) < dp * tp * fsdp:
        return []
    label = f"manual_train_step[dp={dp},tp={tp},fsdp={fsdp},{precision}]"
    text = manual_train_step_jaxpr(precision, dp, tp, fsdp)
    out = check_no_float64(text, label)
    out += check_no_host_callback(text, label)
    if precision == "fp32":
        out += check_no_bf16(text, label)
    else:
        out += check_fp32_island(text, label)
    out += check_manual_train_step_donation(precision, dp, tp, fsdp)
    return out


def scan_entry_points(
    precisions: Sequence[str] = ("fp32", "bf16"),
) -> List[Finding]:
    """The full jaxpr gate: every canonical entry point at every precision
    plus the donation/store-dtype contracts. Zero findings on a healthy
    tree (tier-1 asserts this)."""
    out: List[Finding] = []
    for p in precisions:
        out += scan_train_step(p)
        out += scan_multitask_train_step(p)
        out += scan_resharded_train_step(p)
        out += scan_act(p)
        out += scan_act_select(p)
        out += scan_fused_unroll(p)
        out += scan_manual_train_step(p)
        out += scan_superstep(p)
        out += scan_serve_step(p)
        out += scan_multi_serve_step(p)
        out += scan_liveloop_gather(p)
        out += scan_donation(p)
    # the quantized arm composes with precision the same way everywhere;
    # one trace on the golden path keeps the gate's runtime bounded
    out += scan_serve_step_int8("fp32")
    out += scan_multi_serve_step("fp32", "int8")
    out.sort(key=Finding.sort_key)
    return out


# -------------------------------------------------- source-keyed result cache

# Everything the canonical traces can reach: the jaxprs are pure functions
# of these sources (plus jax itself, which the fast local loop does not
# version — a jax upgrade warrants one uncached run). Directories are
# walked recursively.
_ENTRY_POINT_SOURCES = (
    "config.py",
    "learner.py",
    "megastep.py",
    "models",
    "ops",
    "parallel",
    "replay/block.py",
    "replay/device_store.py",
    "replay/device_sum_tree.py",
    "serve/batcher.py",
    "serve/multi.py",
    "serve/server.py",
    "serve/state_cache.py",
    "liveloop/tap.py",
    "analysis/jaxpr_rules.py",  # the checkers are inputs too
)


def entry_point_source_files() -> List[str]:
    """Absolute paths of every source file the traced entry points (and
    the checkers) depend on."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out: List[str] = []
    for rel in _ENTRY_POINT_SOURCES:
        p = os.path.join(pkg, rel)
        if os.path.isdir(p):
            for root, _dirs, files in os.walk(p):
                out.extend(
                    os.path.join(root, f) for f in files if f.endswith(".py")
                )
        elif os.path.exists(p):
            out.append(p)
    return sorted(out)


def source_fingerprint() -> str:
    """sha256 over (relative path, bytes) of every entry-point source, in
    sorted order — identical tree, identical fingerprint, regardless of
    mtimes or checkout location."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for path in entry_point_source_files():
        h.update(os.path.relpath(path, pkg).replace(os.sep, "/").encode())
        h.update(b"\0")
        with open(path, "rb") as fh:
            h.update(fh.read())
        h.update(b"\0")
    return h.hexdigest()


def scan_entry_points_cached(
    cache_path: str, precisions: Sequence[str] = ("fp32", "bf16")
) -> List[Finding]:
    """scan_entry_points with a result cache keyed on source_fingerprint():
    when none of the traced sources changed, the cached findings are
    returned without importing the model stack or tracing anything —
    `--changed-only --jaxpr` drops from tens of seconds to milliseconds.
    A corrupt/stale/missing cache falls through to a real scan."""
    fp = source_fingerprint()
    try:
        with open(cache_path, encoding="utf-8") as fh:
            data = json.load(fh)
        if data.get("fingerprint") == fp:
            return [Finding(**d) for d in data["findings"]]
    except (OSError, ValueError, KeyError, TypeError):
        pass
    findings = scan_entry_points(precisions)
    tmp = f"{cache_path}.tmp"
    try:
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(
                {
                    "fingerprint": fp,
                    "findings": [f.to_dict() for f in findings],
                },
                fh,
            )
        os.replace(tmp, cache_path)
    except OSError:
        pass  # cache is an optimization; the scan result stands
    return findings
