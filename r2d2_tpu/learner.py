"""Learner (L4): the jitted double-Q, value-rescaled, prioritized update.

Capability parity with the reference Learner (reference worker.py:330-461),
re-architected as ONE pure jitted function over a device mesh:

- double-Q target: a* = argmax_a Q_online(s_{t+n}, a) under stop_gradient,
  evaluated by the target net; y = h(R_n + gamma_n * h^-1(Q_target))
  (worker.py:402-410).
- IS-weighted per-step MSE over valid learning steps (worker.py:419); the
  reference repeats IS weights per step and takes a flat mean over the
  packed steps — identical here as sum(w * td^2 * mask) / sum(mask).
- mixed per-sequence TD priorities computed ON DEVICE in the same jit
  (worker.py:422-425 pays a device->host sync before priority math; here
  only the final (B,) priorities travel to the host).
- Adam(lr=1e-4, eps=1e-3) after global-norm clip 40 (worker.py:344,430).
- target sync folded into the jitted step as a where-select every
  `target_net_update_interval` updates (worker.py:445-447) — no separate
  host-side copy pass.

Per update this runs 2 conv + 2 LSTM evaluations (online, target) vs the
reference's 3 + 3, because `unroll` yields both gather views in one pass
(see models/r2d2.py).

Distribution: with the batch sharded over the mesh's dp axis and params
replicated, XLA inserts the gradient psum automatically — the test suite
asserts 8-fake-device equivalence with the single-device update.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import optax
from flax import struct

from r2d2_tpu.config import R2D2Config
from r2d2_tpu.models.r2d2 import R2D2Network
from r2d2_tpu.ops.priority import mixed_td_priorities
from r2d2_tpu.ops.value_rescale import inverse_value_rescale, value_rescale
from r2d2_tpu.replay.block import rows_as_stored, rows_to_frames
from r2d2_tpu.replay.replay_buffer import SampledBatch
from r2d2_tpu.utils.profiling import scoped


class TrainState(struct.PyTreeNode):
    params: Any
    target_params: Any
    opt_state: Any
    step: jnp.ndarray  # scalar int32


class DeviceBatch(NamedTuple):
    """The device-side view of a SampledBatch (jnp arrays).

    `task` is the multi-task plane's per-sequence task id (B,) int32; it
    defaults to None so every single-task constructor, pytree, and
    donation contract is unchanged (a None leaf is absent from the tree)."""

    obs: jnp.ndarray
    last_action: jnp.ndarray
    last_reward: jnp.ndarray
    hidden: jnp.ndarray
    action: jnp.ndarray
    n_step_reward: jnp.ndarray
    gamma: jnp.ndarray
    burn_in_steps: jnp.ndarray
    learning_steps: jnp.ndarray
    forward_steps: jnp.ndarray
    is_weights: jnp.ndarray
    task: Optional[jnp.ndarray] = None

    @classmethod
    def from_sampled(cls, b: SampledBatch) -> "DeviceBatch":
        return cls(
            obs=jnp.asarray(b.obs),
            last_action=jnp.asarray(b.last_action, jnp.int32),
            last_reward=jnp.asarray(b.last_reward),
            hidden=jnp.asarray(b.hidden),
            action=jnp.asarray(b.action, jnp.int32),
            n_step_reward=jnp.asarray(b.n_step_reward),
            gamma=jnp.asarray(b.gamma),
            burn_in_steps=jnp.asarray(b.burn_in_steps),
            learning_steps=jnp.asarray(b.learning_steps),
            forward_steps=jnp.asarray(b.forward_steps),
            is_weights=jnp.asarray(b.is_weights),
            task=None if b.task is None else jnp.asarray(b.task, jnp.int32),
        )


def _adam(cfg: R2D2Config) -> optax.GradientTransformation:
    """The Adam tail of the optimizer chain — split out so the manual-
    partition step can run EXACTLY these numerics on moment SHARDS (its
    global-norm clip needs cross-shard psums, but Adam is elementwise, so
    the same transformation applies per-shard unchanged)."""
    if cfg.lr_schedule == "cosine":
        # decays over training_steps then HOLDS at lr*lr_final_frac (a
        # resumed run past the horizon keeps the floor, it does not
        # re-warm). Position comes from adam's own update count, which
        # is part of the checkpointed opt_state.
        lr = optax.cosine_decay_schedule(
            cfg.lr, max(cfg.training_steps, 1), alpha=cfg.lr_final_frac
        )
    else:
        lr = cfg.lr
    return optax.adam(lr, eps=cfg.adam_eps)


def make_optimizer(cfg: R2D2Config) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(cfg.grad_norm),
        _adam(cfg),
    )


def init_train_state(cfg: R2D2Config, rng: jax.Array) -> Tuple[R2D2Network, TrainState]:
    from r2d2_tpu.models.r2d2 import init_params

    net, params = init_params(rng, cfg)
    opt_state = make_optimizer(cfg).init(params)
    return net, TrainState(
        params=params,
        target_params=jax.tree.map(jnp.copy, params),
        opt_state=opt_state,
        step=jnp.zeros((), jnp.int32),
    )


def _q_at(q, a):
    """q (..., A), a (...) int -> q[..., a], the bits of `take_along_axis`,
    as a select over A and a sum with one term that is not zero. Indexed
    entry by entry a v5e pays ~11 ns for each f32 (PERF.md finding 46), and
    the gradient is a scatter; the select fuses into its neighbours and its
    gradient is the same select. `where`, never a product with a one-hot:
    the multi-task floor of -1e9 or a non-finite Q of an action NOT taken
    must not reach the sum through a `0 *`."""
    chosen = a[..., None] == jnp.arange(q.shape[-1], dtype=a.dtype)
    return jnp.sum(jnp.where(chosen, q, 0), axis=-1)


def make_loss_fn(cfg: R2D2Config, net: R2D2Network):
    """The per-batch loss closure (params, target_params, batch, denom) ->
    (loss, (priorities, aux)), shared by every train-step builder and by
    the benchmark's correctness check (benchmark/correct.py runs it as its
    own program against the plain reference)."""
    eps = cfg.value_rescale_eps
    # a core whose layers count what they route says how to read what an
    # unroll sowed (`counts_of`); the benchmark's stand-in for the network
    # has no core and counts nothing
    counts_of = getattr(getattr(net, "core", None), "counts_of", None)

    def loss_fn(params, target_params, b: DeviceBatch, denom):
        """denom is the GLOBAL valid-step count: under shard_map it has
        already been psum'd over dp, so per-shard losses are global-loss
        contributions and a grad psum reproduces the global-batch gradient
        exactly (per-shard mask sums differ, so pmean of local ratios would
        not)."""
        # b.task is None on the single-task golden path (a no-op input);
        # multi-task batches condition the dueling head per sequence
        inputs = (b.obs, b.last_action, b.last_reward, b.hidden,
                  b.burn_in_steps, b.learning_steps, b.forward_steps, b.task)
        counted = {}
        if counts_of is not None:
            # what the core counted in the online unroll leaves with the
            # update's metrics
            (q_learn, q_boot_online, mask), sown = net.apply(
                params, *inputs, mutable=["intermediates"]
            )
            counted = counts_of(sown)
        else:
            q_learn, q_boot_online, mask = net.apply(params, *inputs)
        _, q_boot_target, _ = net.apply(target_params, *inputs)
        loss, (priorities, aux) = island(
            q_learn, q_boot_online, q_boot_target, mask, b.action,
            b.n_step_reward, b.gamma, b.is_weights, denom,
        )
        return loss, (priorities, {**aux, **counted})

    def island(q_learn, q_boot_online, q_boot_target, mask, action,
               n_step_reward, gamma, is_weights, denom):
        # fp32 island (precision policy, config.precision): Q-target math,
        # value rescaling, n-step folding, TD/priorities, IS weighting,
        # and the loss reduction stay float32 no matter the compute dtype.
        # The heads already emit f32 (models/r2d2.py _dueling); the casts
        # pin the contract so a future bf16 head cannot silently narrow
        # the target math (tests/test_precision.py asserts the island).
        # double-Q: online selects, target evaluates (worker.py:402-406)
        a_star = jnp.argmax(jax.lax.stop_gradient(q_boot_online), axis=-1)  # (B, L)
        q_tgt = _q_at(q_boot_target, a_star).astype(jnp.float32)
        y = value_rescale(
            n_step_reward.astype(jnp.float32)
            + gamma.astype(jnp.float32) * inverse_value_rescale(q_tgt, eps),
            eps,
        )
        y = jax.lax.stop_gradient(y)

        q_taken = _q_at(q_learn, action).astype(jnp.float32)
        td = y - q_taken
        w = is_weights.astype(jnp.float32)[:, None]
        loss = jnp.sum(w * jnp.square(td) * mask) / denom

        abs_td = jnp.abs(td) * mask
        priorities = mixed_td_priorities(abs_td, mask, cfg.td_mix_eta)
        aux = {
            "q_mean": jnp.sum(q_taken * mask) / denom,
            "target_mean": jnp.sum(y * mask) / denom,
            "td_abs_mean": jnp.sum(abs_td) / denom,
        }
        return loss, (priorities, aux)

    island = scoped(island, "r2d2_loss")
    return loss_fn


def _raw_train_step(cfg: R2D2Config, net: R2D2Network, axis_name: Optional[str] = None):
    """The un-jitted (state, batch) -> (state, metrics, priorities) body,
    shared by the host-batch and device-store (fused) entry points.

    axis_name=None: pure single-program body — under plain jit with the
    batch sharded over a mesh, XLA inserts the gradient all-reduce itself.
    axis_name="dp": the body runs per-shard under shard_map and all-reduces
    gradients/metrics with an explicit lax.psum over the named axis (exact
    because the loss denominator is psum'd globally first; the collective
    rides ICI on a real slice)."""
    optimizer = make_optimizer(cfg)
    loss_fn = make_loss_fn(cfg, net)

    def train_step(state: TrainState, b: DeviceBatch):
        if cfg.zero_state_replay:
            # zero-state ablation (R2D2 paper's baseline replay strategy):
            # discard the stored recurrent state; one site covers every
            # plane because all step builders share this body
            b = b._replace(hidden=jnp.zeros_like(b.hidden))
        # valid learning steps: mask row i has exactly learning_steps[i] ones
        denom = jnp.sum(b.learning_steps).astype(jnp.float32)
        if axis_name is not None:
            denom = jax.lax.psum(denom, axis_name)
        denom = jnp.maximum(denom, 1.0)
        (loss, (priorities, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, state.target_params, b, denom
        )
        return apply(state, grads, loss, aux, priorities)

    def apply(state, grads, loss, aux, priorities):
        if axis_name is not None:
            grads = jax.lax.psum(grads, axis_name)
            loss = jax.lax.psum(loss, axis_name)
            aux = jax.tree.map(lambda x: jax.lax.psum(x, axis_name), aux)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        step = state.step + 1
        # target sync every interval, inside the compiled step
        sync = (step % cfg.target_net_update_interval) == 0
        target_params = jax.tree.map(
            lambda t, p: jnp.where(sync, p, t), state.target_params, params
        )
        metrics = {
            "loss": loss,
            "grad_norm": optax.global_norm(grads),
            **aux,
        }
        new_state = TrainState(
            params=params, target_params=target_params, opt_state=opt_state, step=step
        )
        return new_state, metrics, priorities

    apply = scoped(apply, "r2d2_optimizer")
    return train_step


def make_train_step(cfg: R2D2Config, net: R2D2Network, donate: bool = True):
    """Jitted (state, batch) -> (state, metrics, priorities) over a
    host-assembled DeviceBatch."""
    raw = _raw_train_step(cfg, net)
    return jax.jit(raw, donate_argnums=(0,) if donate else ())


def _windows(field, b, first, length: int):
    """field (blocks, n) -> (B, length): `field[b[:, None], clip(first[:, None]
    + arange(length), 0, n - 1)]` for first >= 0, read as ONE run of each row.

    Indexed entry by entry, as that formula says it, a v5e pays ~9 ns an
    index whatever it reads: the five scalar fields were 0.19 of the 0.38 ms
    of nature's gather and 0.87 of lru's 2.08 (PERF.md finding 41). So: B row
    reads, each row extended by its last entry (what the clip repeats), then a
    barrel shifter brings each row's window to the front: for every bit of
    `first`, a static roll by 2**k, selected per row. Static shapes, selects
    only: every bit of every entry is the row's. (A roll wraps the row's head
    round to its tail, past the end of every window.)"""
    n = field.shape[1]
    rows = field[b]
    rows = jnp.concatenate([rows, jnp.broadcast_to(rows[:, -1:], (rows.shape[0], length - 1))], axis=1)
    first = jnp.clip(first, 0, n - 1)
    for k in range((n - 1).bit_length()):
        rows = jnp.where(((first >> k) & 1 == 1)[:, None], jnp.roll(rows, -(1 << k), axis=1), rows)
    return rows[:, :length]


def make_store_gather(cfg: R2D2Config, as_stored: bool = False):
    """(stores, b, s, is_weights) -> DeviceBatch: in-jit clamped-window
    gather straight out of the HBM-resident stores. b is a block index
    LOCAL to whatever store shard the caller passes (the whole store under
    plain jit; one dp shard under shard_map).

    `obs` is canonical (B, T, *obs_shape) frames whatever order the store
    keeps a frame's bytes in (replay/block.py). as_stored=True hands the
    frames back in the store's own order, (B, T, *blocked_shape): a slice and
    a reshape of the gathered rows with (B, T) merged (replay/block.
    rows_as_stored: the frame index stays one axis from this gather to the
    first conv, which merges (B, T) itself). The step programs ask for that;
    the encoder takes either (models/encoders.BlockedConv).

    A sampled sequence is one window of its slot. accumulator.finish stores
    burn_in[s] = min(s L + first_burn, burn_in), so win = first_burn + s L -
    burn_in[s] = max(0, first_burn + s L - burn_in) lies in [0, (S - 1) L] =
    [0, block_length - L], and win + burn_in + L + 1 <= block_slot_len: the
    first T - F + 1 rows of a window never leave their slot, for every preset
    (tests/test_store_gather.py pins it); only the last F - 1 can meet the
    clip, and do in the last sequence of every full block. The per-step
    scalar fields are read as such windows (`_windows`, which keeps the clip's
    answer for ANY start, so nothing traced rests on this geometry).
    The frames keep one clipped index each: a frame is 7 KiB, its index costs
    nothing beside it, and that gather already ran at 80-90 % of the HBM's
    pace; B windows copied by a loop of dynamic slices ran at half of it
    (PERF.md finding 41.1)."""
    L, T = cfg.learning_steps, cfg.seq_len
    slot = cfg.block_slot_len
    to_frames = rows_as_stored if as_stored else rows_to_frames

    def gather_batch(stores, b, s, is_weights) -> DeviceBatch:
        burn = stores["burn_in"][b, s]
        learn = stores["learning"][b, s]
        fwd = stores["forward"][b, s]
        first_burn = stores["burn_in"][b, 0]
        start = first_burn + s * L
        win = start - burn
        t = jnp.arange(T, dtype=jnp.int32)
        rows = jnp.clip(win[:, None] + t[None, :], 0, slot - 1)
        # obs: frames stored as lane-aligned rows (replay/block.py). Gathered
        # with ONE index over the flattened (block * slot) axis (a bitcast of
        # the row-major store), not with a (block, row) pair: on the v5e
        # (libtpu 0.0.34) the two-index gather of (56, 128) uint8 slices
        # compiles and then halts the core on its first execution, alone or
        # inside the step programs; the one-index form runs, and faster than
        # any other that was tried (PERF.md findings 25.2, 41.1). mode="clip":
        # the indices are in bounds by the clip above, and jnp.take's default
        # ("fill") compiled to a select against the fill value over the whole
        # batch, a pass that guarded nothing
        obs = stores["obs"]
        flat = obs.reshape(obs.shape[0] * slot, *obs.shape[2:])
        return DeviceBatch(
            obs=to_frames(
                jnp.take(flat, b[:, None] * slot + rows, axis=0, mode="clip"),
                cfg.obs_shape, cfg.resolved_frame_block,
            ),
            last_action=_windows(stores["last_action"], b, win, T),
            last_reward=_windows(stores["last_reward"], b, win, T),
            hidden=stores["hidden"][b, s],
            action=_windows(stores["action"], b, s * L, L),
            n_step_reward=_windows(stores["n_step_reward"], b, s * L, L),
            gamma=_windows(stores["gamma"], b, s * L, L),
            burn_in_steps=burn,
            learning_steps=learn,
            forward_steps=fwd,
            is_weights=is_weights,
            # the task store exists only when the config runs multi-task
            # (replay/block.store_field_specs) — single-task stores keep
            # their exact field set and this stays a None leaf
            task=stores["task"][b, s] if "task" in stores else None,
        )

    return gather_batch


def make_fused_multi_train_step(
    cfg: R2D2Config, net: R2D2Network, num_steps: int, donate: bool = True
):
    """The train step over a DEVICE-RESIDENT replay store
    (replay/device_store.py): K updates in ONE dispatch, a lax.scan over
    stacked sample coordinates in which each iteration gathers its batch
    in-jit straight from HBM and applies the full update (in-jit target sync
    included). Only the (K, B) coordinates cross the host->device boundary.

    K = 1 is the same program with one iteration; there is no other way to
    run an update on an HBM store. Each iteration is numerically identical to
    make_train_step on the equivalent host-assembled batch (pinned by
    tests/test_device_store.py for K in {1, 4}). The host's per-call launch
    cost is paid once per K updates (whether that still pays on a directly
    attached chip is a benchmark question, ROADMAP S2). The semantic trade is
    that priorities and new blocks apply to the tree at K-update granularity:
    the reference's own pipeline already tolerates a deeper lag (its batch
    queue + learner prefetch hold ~12 batches, reference worker.py:364-371).

    Signature: (state, stores, b, s, w) with b/s/w of shape (K, B);
    returns (state, metrics-of-last-step, priorities (K, B))."""
    core = make_multi_update_core(cfg, net, num_steps)

    # the module keeps its name `jit_multi` (a trace's module line and the
    # benchmark's `step_program` pattern read it); the core is `r2d2_update`
    def multi(state: TrainState, stores, b, s, w):
        return core(state, stores, b, s, w)

    return jax.jit(multi, donate_argnums=(0,) if donate else ())


def make_multi_update_core(
    cfg: R2D2Config, net: R2D2Network, num_steps: int,
    axis_name: Optional[str] = None,
    is_from_priorities: bool = False,
):
    """The un-jitted K-update scan body shared by
    make_fused_multi_train_step and megastep.make_megastep — one
    definition so the two dispatch paths cannot diverge.

    axis_name="dp": the body runs per-shard under shard_map — gathers hit
    the LOCAL store shard and gradients/denominators psum over the axis
    (exact thanks to the globally-psum'd loss denominator); b/s/w are then
    the local (K, B/dp) coordinate stacks.

    is_from_priorities=True (needs axis_name): w carries RAW sampled tree
    priorities; each scan iteration normalizes ITS OWN batch against that
    update's batch-global minimum via a pmin over the axis. This is how the
    multi-host replay gets exact single-tree IS semantics with zero
    cross-host control traffic (replay/multihost_store.py): each host only
    knows its local priorities, the collective finds the global min."""
    if is_from_priorities and axis_name is None:
        raise ValueError("is_from_priorities needs an axis_name (pmin)")
    raw = _raw_train_step(cfg, net, axis_name=axis_name)
    gather_batch = scoped(make_store_gather(cfg, as_stored=True), "r2d2_gather")

    def multi(state: TrainState, stores, b, s, w):
        if b.shape[0] != num_steps:
            raise ValueError(
                f"coordinate stack has {b.shape[0]} steps, expected {num_steps}"
            )

        def body(state, xs):
            bb, ss, ww = xs
            if is_from_priorities:
                # same formula as SumTree.sample (zero-priority leaves clamp
                # to the min -> weight 1.0)
                p = ww
                pos_min = jnp.min(jnp.where(p > 0, p, jnp.inf))
                min_p = jax.lax.pmin(pos_min, axis_name)
                min_p = jnp.where(jnp.isfinite(min_p), min_p, 1.0)
                ww = jnp.power(jnp.maximum(p, min_p) / min_p, -cfg.is_exponent)
            batch = gather_batch(stores, bb, ss, ww)
            state, metrics, prios = raw(state, batch)
            return state, (metrics, prios)

        state, (metrics, prios) = jax.lax.scan(body, state, (b, s, w))
        return state, jax.tree.map(lambda x: x[-1], metrics), prios

    # the device scope of the whole K-update scan: every dispatch path that
    # shares this core (megastep, sharded megastep, supersteps) carries it
    return scoped(multi, "r2d2_update")


def make_sharded_fused_multi_train_step(
    cfg: R2D2Config, net: R2D2Network, mesh, num_steps: int, donate: bool = True,
    is_from_priorities: bool = False,
):
    """K updates in ONE shard_map dispatch over a dp-SHARDED replay store
    (replay/sharded_store.ShardedDeviceReplay, replay/multihost_store): the
    multi-chip form of make_fused_multi_train_step, for every K >= 1. Each
    device scans K updates gathering its (B/dp) sub-batches from its LOCAL
    store shard (no cross-device data-plane traffic) and psums gradients
    over dp per update (ICI). Params/opt state replicated in and out.

    Signature: (state, stores, b, s, w) with b/s/w of shape (K, dp, B/dp)
    and b LOCAL to each shard; returns (state, metrics-of-last-step,
    priorities (K, dp, B/dp)). is_from_priorities: see
    make_multi_update_core — w carries raw priorities, normalized per
    update with a pmin over dp (the multihost K-dispatch path)."""
    from jax.sharding import PartitionSpec as P
    from r2d2_tpu.parallel.jax_compat import shard_map
    from r2d2_tpu.parallel.mesh import dp_manual_axes

    multi = make_multi_update_core(
        cfg, net, num_steps, axis_name="dp", is_from_priorities=is_from_priorities
    )

    def body(state: TrainState, stores, b, s, w):
        # local views: stores (nb/dp, ...), b/s/w (K, 1, B/dp)
        state, metrics, prios = multi(state, stores, b[:, 0], s[:, 0], w[:, 0])
        return state, metrics, prios[:, None]

    # P("dp") is a PREFIX spec for the stores dict: it applies to every
    # field array.
    # dp_manual_axes: with tp > 1 the map is MANUAL over dp only — the
    # mesh's tp axis stays GSPMD-auto, so params arriving with tp
    # NamedShardings (parallel/mesh.train_state_shardings) are
    # Megatron-partitioned inside the per-dp-shard body by the compiler,
    # composing dp×tp; with tp == 1 it is fully manual (Pallas core).
    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P("dp"), P(None, "dp"), P(None, "dp"), P(None, "dp")),
        out_specs=(P(), P(), P(None, "dp")),
        axis_names=dp_manual_axes(mesh),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def make_stacked_batch_train_step(
    cfg: R2D2Config, net: R2D2Network, num_steps: int, donate: bool = True
):
    """K train steps in ONE dispatch over a PRE-GATHERED stacked batch: the
    tiered plane's consumer. make_fused_multi_train_step's scan gathers each
    iteration's batch from the HBM-resident store; here the gather already
    happened on host at stage time (replay/tiered_store.py), so the scan is
    re-pointed at the staging slab — a DeviceBatch whose leaves carry a
    leading (K, ...) axis — and each iteration just slices its batch off.

    Donating the batch (argnum 1) is what closes the staging ring: the
    consumed slab's HBM is recycled into the next device_put instead of
    accumulating a third live copy.

    Signature: (state, stacked DeviceBatch with (K, B, ...) leaves) ->
    (state, metrics-of-last-step, priorities (K, B))."""
    raw = _raw_train_step(cfg, net)

    def multi(state: TrainState, stacked: DeviceBatch):
        if stacked.obs.shape[0] != num_steps:
            raise ValueError(
                f"staged batch has {stacked.obs.shape[0]} steps, "
                f"expected {num_steps}"
            )

        def body(state, batch):
            state, metrics, prios = raw(state, batch)
            return state, (metrics, prios)

        state, (metrics, prios) = jax.lax.scan(body, state, stacked)
        return state, jax.tree.map(lambda x: x[-1], metrics), prios

    return jax.jit(multi, donate_argnums=(0, 1) if donate else ())


def make_manual_train_step(cfg: R2D2Config, mesh, donate: bool = True):
    """Fully-manual shard_map train step over ALL mesh axes — the tp×fsdp
    path that GSPMD miscompiles (PR 14: tp-sharded params on a 3-axis mesh
    break the recurrent scan's forward; config.resolved_partitioning routes
    here instead of blocking).

    Partitioning (every spec read from parallel/sharding_map's table, so
    this step and the GSPMD planes cannot disagree about placement):

      tp    Megatron splits inside the per-shard network itself
            (R2D2Network.from_config(manual_tp=tp)): column-parallel gate
            kernels with an explicit per-step all-gather seam at the gate
            matmul (models/lstm._gates), column/row dueling heads with a
            psum seam (models/r2d2.RowDense), column-parallel encoder
            Dense_0. Params replicated over dp and fsdp.
      dp    batch data parallelism, explicit gradient psum.
      fsdp  ZeRO-2: the batch ALSO splits over fsdp (manual_data_axes), so
            each fsdp member owns gradients for a distinct batch slice and
            the gradient lands on the Adam moment shards via a TRUE
            reduce-scatter (psum_scatter); Adam runs on shards; updates
            all-gather back to replicated params.

    Gradient correctness under manual tp (validated bit-level against the
    unsharded reference): the per-device AD gradient equals the derivative
    of the SUM of all tp members' objectives w.r.t. the local shard, so
    with the loss scaled by 1/tp inside value_and_grad, tp-SHARDED leaves'
    local grads are already exact per-shard (no collective), while
    REPLICATED leaves (convs, row-parallel biases, deeper encoder Dense,
    LRU params) need an extra psum over tp to sum their members'
    contributions.

    The global-norm clip reproduces optax.clip_by_global_norm exactly:
    per-leaf shard sum-of-squares are psum'd over exactly the axes that
    leaf is sharded over (tp for table-sharded leaves, fsdp for scattered
    ones), summed, sqrt'd — the same global norm every device, then the
    identical where/scale formula. Adam itself is elementwise, so the
    _adam(cfg) tail runs unchanged on moment shards.

    Signature: jitted (state, batch) -> (state, metrics, priorities) where
    state leaves are placed per train_state_shardings(mesh) and batch
    leaves are sharded over (dp, fsdp) on their leading axis
    (parallel.manual_batch_sharding)."""
    from jax.sharding import PartitionSpec as P
    from r2d2_tpu.parallel.jax_compat import shard_map
    from r2d2_tpu.parallel.mesh import manual_data_axes
    from r2d2_tpu.parallel.sharding_map import (
        moment_spec_for,
        process_name,
        spec_for,
        tree_pspecs,
    )

    tp = int(mesh.shape.get("tp", 1))
    data_axes = manual_data_axes(mesh)
    n_data = 1
    for a in data_axes:
        n_data *= int(mesh.shape[a])
    if cfg.batch_size % n_data != 0:
        raise ValueError(
            f"batch_size {cfg.batch_size} not divisible by dp*fsdp={n_data}"
        )
    has_fsdp = "fsdp" in mesh.axis_names and int(mesh.shape["fsdp"]) > 1

    # the per-shard network: kernels declared at their LOCAL (1/tp) widths,
    # collective seams inside the module bodies
    local_net = R2D2Network.from_config(cfg, manual_tp=tp)
    loss_fn = make_loss_fn(cfg, local_net)
    adam = _adam(cfg)

    # abstract GLOBAL TrainState -> spec trees + per-param-leaf grad plan
    template = jax.eval_shape(
        lambda k: init_train_state(cfg, k)[1], jax.random.PRNGKey(0)
    )
    state_specs = tree_pspecs(template, mesh)
    params_treedef = jax.tree.structure(template.params)
    grad_plan = []  # aligned with jax.tree.leaves(params): (tp_sharded, fdim)
    for path, leaf in jtu.tree_flatten_with_path(template.params)[0]:
        name = process_name(path)
        pspec = tuple(spec_for(name, leaf, mesh))
        mspec = tuple(moment_spec_for(name, leaf, mesh))
        tp_sharded = tp > 1 and "tp" in pspec
        fdim = mspec.index("fsdp") if (has_fsdp and "fsdp" in mspec) else None
        grad_plan.append((tp_sharded, fdim))

    batch_spec = P(data_axes)
    in_batch = DeviceBatch(*([batch_spec] * len(DeviceBatch._fields)))
    if cfg.num_tasks <= 1:
        in_batch = in_batch._replace(task=None)

    def body(state: TrainState, b: DeviceBatch):
        if cfg.zero_state_replay:
            b = b._replace(hidden=jnp.zeros_like(b.hidden))
        denom = jnp.sum(b.learning_steps).astype(jnp.float32)
        denom = jnp.maximum(jax.lax.psum(denom, data_axes), 1.0)

        def objective(params, target_params, b, denom):
            loss, extras = loss_fn(params, target_params, b, denom)
            # 1/tp balances AD's accumulation across the tp group (see
            # docstring); exact no-op at tp=1
            return loss / tp, extras

        (loss, (priorities, aux)), grads = jax.value_and_grad(
            objective, has_aux=True
        )(state.params, state.target_params, b, denom)

        # summing the scaled per-member losses over every axis recovers the
        # global loss (tp members carry identical copies at weight 1/tp)
        loss = jax.lax.psum(loss, data_axes + ("tp",))
        aux = jax.tree.map(lambda x: jax.lax.psum(x, data_axes), aux)

        # gradient reduction per the plan: dp always; +tp for replicated
        # leaves; fsdp by reduce-scatter onto the moment shard's dim when
        # it has one (ZeRO-2), full psum otherwise
        def reduce_grad(g, tp_sharded, fdim):
            axes = ["dp"]
            if tp > 1 and not tp_sharded:
                axes.append("tp")
            if has_fsdp and fdim is None:
                axes.append("fsdp")
            g = jax.lax.psum(g, tuple(axes))
            if has_fsdp and fdim is not None:
                g = jax.lax.psum_scatter(
                    g, "fsdp", scatter_dimension=fdim, tiled=True
                )
            return g

        flat_g = [
            reduce_grad(g, tps, fd)
            for g, (tps, fd) in zip(jax.tree.leaves(grads), grad_plan)
        ]

        # global-norm clip == optax.clip_by_global_norm on the full grads:
        # group leaves by which axes still shard them after reduction
        partial_sq: Dict[tuple, jnp.ndarray] = {}
        for g, (tps, fd) in zip(flat_g, grad_plan):
            axes = []
            if tps:
                axes.append("tp")
            if fd is not None:
                axes.append("fsdp")
            key = tuple(axes)
            sq = jnp.sum(jnp.square(g))
            partial_sq[key] = partial_sq.get(key, 0.0) + sq
        total_sq = jnp.float32(0.0)
        for axes, sq in partial_sq.items():
            total_sq = total_sq + (jax.lax.psum(sq, axes) if axes else sq)
        gnorm = jnp.sqrt(total_sq)
        trigger = gnorm < cfg.grad_norm
        flat_g = [
            jnp.where(trigger, g, (g / gnorm.astype(g.dtype)) * cfg.grad_norm)
            for g in flat_g
        ]
        grads = jax.tree.unflatten(params_treedef, flat_g)

        # Adam on shards; updates gather back to replicated param layout
        clip_state, adam_state = state.opt_state
        updates, adam_state = adam.update(grads, adam_state)
        if has_fsdp:
            flat_u = [
                jax.lax.all_gather(u, "fsdp", axis=fd, tiled=True)
                if fd is not None
                else u
                for u, (_, fd) in zip(jax.tree.leaves(updates), grad_plan)
            ]
            updates = jax.tree.unflatten(params_treedef, flat_u)
        params = optax.apply_updates(state.params, updates)

        step = state.step + 1
        sync = (step % cfg.target_net_update_interval) == 0
        target_params = jax.tree.map(
            lambda t, p: jnp.where(sync, p, t), state.target_params, params
        )
        metrics = {"loss": loss, "grad_norm": gnorm, **aux}
        new_state = TrainState(
            params=params,
            target_params=target_params,
            opt_state=(clip_state, adam_state),
            step=step,
        )
        return new_state, metrics, priorities

    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=(state_specs, in_batch),
        out_specs=(state_specs, P(), batch_spec),
        axis_names=None,  # fully manual over EVERY mesh axis
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())
